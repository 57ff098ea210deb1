"""MARL system tests: env invariants, IC3Net, short FLGW training runs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.marl import env as env_mod
from repro.marl import ic3net
from repro.marl import train as train_mod


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), a=st.integers(1, 6),
       size=st.integers(3, 8))
def test_env_positions_stay_in_bounds(seed, a, size):
    cfg = env_mod.EnvConfig(n_agents=a, size=size, max_steps=8)
    key = jax.random.PRNGKey(seed)
    state = env_mod.reset(key, cfg)
    for i in range(8):
        k = jax.random.fold_in(key, i)
        actions = jax.random.randint(k, (a,), 0, env_mod.N_ACTIONS)
        state, rew, done = env_mod.step(state, actions, cfg)
        assert (np.asarray(state.pos) >= 0).all()
        assert (np.asarray(state.pos) < size).all()
        assert rew.shape == (a,)


def test_env_arrived_agents_freeze_and_success():
    cfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=10)
    state = env_mod.EnvState(
        pos=jnp.array([[1, 1], [0, 0]], jnp.int32),
        prey=jnp.array([1, 1], jnp.int32),
        arrived=jnp.zeros((2,), bool), t=jnp.zeros((), jnp.int32))
    state, rew, done = env_mod.step(state, jnp.array([0, 0]), cfg)
    assert bool(state.arrived[0]) and not bool(state.arrived[1])
    assert float(rew[0]) > 0 > float(rew[1])
    # agent 1 walks to the prey
    state, _, _ = env_mod.step(state, jnp.array([0, 2]), cfg)  # down
    state, _, done = env_mod.step(state, jnp.array([0, 4]), cfg)  # right
    assert bool(env_mod.success(state))
    assert bool(done)


def test_env_observation_shape_and_prey_visibility():
    cfg = env_mod.EnvConfig(n_agents=3, size=5, vision=1)
    state = env_mod.reset(jax.random.PRNGKey(0), cfg)
    obs = env_mod.observe(state, cfg)
    assert obs.shape == (3, env_mod.obs_dim(cfg))
    off = np.abs(np.asarray(state.prey)[None] - np.asarray(state.pos))
    seen = (off <= cfg.vision).all(axis=1)
    np.testing.assert_array_equal(np.asarray(obs[:, -1]) > 0.5, seen)


@pytest.mark.parametrize("groups,path", [(1, "masked"), (4, "masked"),
                                         (4, "grouped")])
def test_ic3net_short_training_runs(groups, path):
    cfg = ic3net.IC3NetConfig(hidden=32, flgw_groups=groups, flgw_path=path)
    ecfg = env_mod.EnvConfig(n_agents=3, size=4, max_steps=8)
    tcfg = train_mod.TrainConfig(batch=4)
    params, hist = train_mod.train(cfg, ecfg, tcfg, iterations=3)
    assert len(hist) == 3
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_ic3net_gate_controls_communication():
    """Gate=0 must zero the communication input (learning when to talk)."""
    cfg = ic3net.IC3NetConfig(hidden=16, n_agents=3, n_actions=5, obs_dim=7)
    params, _ = ic3net.init(jax.random.PRNGKey(0), cfg)
    obs = jnp.ones((3, 7))
    hc, _ = ic3net.initial_state(cfg)
    hc = (jnp.ones_like(hc[0]) * 0.3, hc[1])  # nonzero hidden so comm != 0
    lg_on, _, _, _ = ic3net.policy_step(params, cfg, obs, hc,
                                        jnp.ones((3,)))
    lg_off, _, _, _ = ic3net.policy_step(params, cfg, obs, hc,
                                         jnp.zeros((3,)))
    assert not np.allclose(np.asarray(lg_on), np.asarray(lg_off))


def test_ic3net_learns_more_than_random_on_tiny_task():
    """Sanity: success rate after training ≥ before (tiny budget, loose)."""
    cfg = ic3net.IC3NetConfig(hidden=32)
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, vision=2, max_steps=6)
    tcfg = train_mod.TrainConfig(batch=16)
    params, hist = train_mod.train(cfg, ecfg, tcfg, iterations=40, seed=1)
    first = np.mean([h["success"] for h in hist[:5]])
    last = np.mean([h["success"] for h in hist[-5:]])
    assert last >= first - 0.05


def test_scan_loop_matches_host_loop_on_predator_prey():
    """The on-device lax.scan loop must reproduce the seed host loop:
    same seed + same config ⇒ same success/loss trajectory and params."""
    cfg = ic3net.IC3NetConfig(hidden=16)
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=6)
    tcfg = train_mod.TrainConfig(batch=4)
    p_host, h_host = train_mod.train(cfg, ecfg, tcfg, iterations=6, seed=0,
                                     host_loop=True)
    p_scan, h_scan = train_mod.train(cfg, ecfg, tcfg, iterations=6, seed=0,
                                     log_every=2)
    np.testing.assert_allclose([h["success"] for h in h_host],
                               [h["success"] for h in h_scan], atol=1e-6)
    np.testing.assert_allclose([h["loss"] for h in h_host],
                               [h["loss"] for h in h_scan], rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p_host), jax.tree.leaves(p_scan)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("env_name",
                         ["predator_prey", "traffic_junction", "spread"])
def test_engine_trains_every_registered_env(env_name):
    from repro.marl import envs
    env, ecfg = envs.make(env_name)
    cfg = ic3net.IC3NetConfig(hidden=16, flgw_groups=4)
    tcfg = train_mod.TrainConfig(batch=2)
    _, hist = train_mod.train(cfg, ecfg, tcfg, iterations=2, seed=0,
                              env=env_name)
    assert len(hist) == 2
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert all(0.0 <= h["success"] <= 1.0 for h in hist)


def test_sparsity_schedule_warmup_runs_dense_then_sparse():
    """G-ramp: the warmup iterations run the dense path inside the scan,
    then the FLGW mask switches on — the loop must stay finite across the
    boundary and train the grouping matrices afterwards."""
    from repro.core.schedule import SparsitySchedule
    cfg = ic3net.IC3NetConfig(hidden=16, flgw_groups=4)
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=6)
    tcfg = train_mod.TrainConfig(batch=4)
    sched = SparsitySchedule(groups=4, warmup_steps=3)
    params, hist = train_mod.train(cfg, ecfg, tcfg, iterations=6, seed=0,
                                   schedule=sched)
    assert len(hist) == 6
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert sched.groups_at(0) == 1 and sched.groups_at(3) == 4
    # grouping matrices exist and received updates after warmup
    assert "ig" in params["enc"]
    # the sparsity metric must describe the compute that actually ran:
    # 0 while the dense warmup branch executes, ~1-1/G afterwards
    assert all(h["mask_sparsity"] == 0.0 for h in hist[:3])
    assert all(h["mask_sparsity"] > 0.5 for h in hist[3:])


def test_masked_vs_grouped_training_trajectories_close():
    """The compact grouped path inside the scan must track the masked
    (full-FLOPs numerical oracle) training run: same seed, same config ⇒
    near-identical loss/success trajectories (small drift allowed — the
    capacity-balanced layout spills a few rows, and dIG/dOG use the
    sparse-restricted STE)."""
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=6)
    tcfg = train_mod.TrainConfig(batch=8)
    hists = {}
    for path in ("masked", "grouped"):
        cfg = ic3net.IC3NetConfig(hidden=16, flgw_groups=2, flgw_path=path)
        _, hists[path] = train_mod.train(cfg, ecfg, tcfg, iterations=8,
                                         seed=0)
    lm = np.array([h["loss"] for h in hists["masked"]])
    lg = np.array([h["loss"] for h in hists["grouped"]])
    np.testing.assert_allclose(lg, lm, rtol=0.5, atol=0.5)
    sm = np.array([h["success"] for h in hists["masked"]])
    sg = np.array([h["success"] for h in hists["grouped"]])
    assert np.abs(sg - sm).max() <= 0.25


def test_grouped_scan_loop_matches_host_loop():
    """Plan-cache parity: the scan carry's refreshed plans must reproduce
    the host loop's explicit refresh — same params and trajectories."""
    from repro.core.schedule import SparsitySchedule
    cfg = ic3net.IC3NetConfig(hidden=16, flgw_groups=4, flgw_path="grouped")
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=6)
    tcfg = train_mod.TrainConfig(batch=4)
    sched = SparsitySchedule(groups=4, refresh_every=2)
    p_host, h_host = train_mod.train(cfg, ecfg, tcfg, iterations=4, seed=0,
                                     schedule=sched, host_loop=True)
    p_scan, h_scan = train_mod.train(cfg, ecfg, tcfg, iterations=4, seed=0,
                                     schedule=sched, log_every=2)
    np.testing.assert_allclose([h["loss"] for h in h_host],
                               [h["loss"] for h in h_scan], rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p_host), jax.tree.leaves(p_scan)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_plan_refresh_reuses_stale_plans_until_boundary():
    """refresh_every=k: iterations with it % k != 0 must pass the carried
    (stale) plans through bit-identically; it % k == 0 must re-encode from
    the current grouping matrices."""
    from repro.core.schedule import SparsitySchedule
    cfg = ic3net.IC3NetConfig(hidden=16, obs_dim=7, flgw_groups=4,
                              flgw_path="grouped")
    params, _ = ic3net.init(jax.random.PRNGKey(0), cfg)
    fresh = ic3net.encode_plans(params, cfg)
    # a deliberately wrong ("stale") cache: plans of different params
    other, _ = ic3net.init(jax.random.PRNGKey(1), cfg)
    stale = ic3net.encode_plans(other, cfg)
    sched = SparsitySchedule(groups=4, refresh_every=3)
    for it in range(7):
        got = jax.jit(train_mod.maybe_refresh_plans,
                      static_argnames=("cfg", "schedule"))(
            params, stale, it, cfg=cfg, schedule=sched)
        want = fresh if it % 3 == 0 else stale
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grouped_scan_on_change_matches_host_loop():
    """Change-driven refresh inside the scan carry: the hash compare +
    conditional re-encode must mirror the host loop exactly (same jitted
    maybe_refresh), so trajectories and params agree bit-for-bit-ish."""
    from repro.core.schedule import SparsitySchedule
    cfg = ic3net.IC3NetConfig(hidden=16, flgw_groups=4, flgw_path="grouped")
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=6)
    tcfg = train_mod.TrainConfig(batch=4, lr=0.05)   # lr high: masks churn
    sched = SparsitySchedule(groups=4, refresh="on_change")
    p_host, h_host = train_mod.train(cfg, ecfg, tcfg, iterations=5, seed=0,
                                     schedule=sched, host_loop=True)
    p_scan, h_scan = train_mod.train(cfg, ecfg, tcfg, iterations=5, seed=0,
                                     schedule=sched, log_every=2)
    np.testing.assert_allclose([h["loss"] for h in h_host],
                               [h["loss"] for h in h_scan], rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p_host), jax.tree.leaves(p_scan)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_env_shim_still_resolves_with_deprecation_warning():
    """repro.marl.env stays importable (seed API) but warns, pointing at
    the envs registry."""
    import importlib
    import warnings as w

    from repro.marl import env as shim
    with w.catch_warnings(record=True) as caught:
        w.simplefilter("always")
        shim = importlib.reload(shim)
    assert any(issubclass(c.category, DeprecationWarning) for c in caught)
    assert any("repro.marl.envs" in str(c.message) for c in caught)
    from repro.marl.envs import predator_prey
    assert shim.reset is predator_prey.reset
    assert shim.EnvConfig is predator_prey.EnvConfig


def test_grouped_stale_plans_actually_change_training():
    """Amortization must be real: with a learning rate high enough to move
    the grouping matrices, refresh_every=4 must diverge from refresh_every=1
    (if plans were silently re-encoded per projection the two would match)."""
    from repro.core.schedule import SparsitySchedule
    cfg = ic3net.IC3NetConfig(hidden=16, flgw_groups=4, flgw_path="grouped")
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=6)
    tcfg = train_mod.TrainConfig(batch=4, lr=0.05)
    losses = {}
    for k in (1, 4):
        sched = SparsitySchedule(groups=4, refresh_every=k)
        _, hist = train_mod.train(cfg, ecfg, tcfg, iterations=6, seed=0,
                                  schedule=sched)
        losses[k] = np.array([h["loss"] for h in hist])
        assert np.isfinite(losses[k]).all()
    assert not np.allclose(losses[1], losses[4])


def test_encode_happens_once_per_refresh_not_per_projection():
    """Regression guard for the OSEL amortization: tracing one training
    chunk must hit make_plan exactly once per FLGW layer (inside the
    refresh cond), independent of iterations/batch/rollout length — NOT
    once per projection call (the plan=None fallback)."""
    from repro.analysis.contracts import trace_counter
    from repro.core import grouped
    from repro.core.schedule import SparsitySchedule
    cfg = ic3net.IC3NetConfig(hidden=16, flgw_groups=4, flgw_path="grouped")
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=6)
    tcfg = train_mod.TrainConfig(batch=3)
    from repro.marl import envs
    e = envs.get("predator_prey")
    cfg2, key, params, opt_state = train_mod._init(cfg, ecfg, e, seed=0)
    plans = ic3net.encode_plans(params, cfg2)
    n_flgw_layers = len(plans.plans)
    assert n_flgw_layers == 5    # enc, lstm_x, lstm_h, comm, policy
    with trace_counter(grouped, "make_plan") as calls:
        # eager _scan_chunk: lax.scan traces the body exactly once
        train_mod._scan_chunk(params, opt_state, key, plans,
                              jnp.zeros((), jnp.int32), 4, cfg2, ecfg,
                              tcfg, e,
                              SparsitySchedule(groups=4, refresh_every=2))
    assert calls.count == n_flgw_layers, calls.count


def test_history_carries_throughput_and_sparsity_metrics():
    """Per-iteration metrics from inside the scan: realised mask sparsity
    plus host-derived steps/s and estimated sparse GFLOPS."""
    cfg = ic3net.IC3NetConfig(hidden=16, flgw_groups=4)
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=6)
    _, hist = train_mod.train(cfg, ecfg, train_mod.TrainConfig(batch=2),
                              iterations=3, seed=0)
    for h in hist:
        assert 0.0 <= h["mask_sparsity"] < 1.0
        assert h["steps_per_s"] > 0
        assert h["env_steps_per_s"] == pytest.approx(
            h["steps_per_s"] * 2 * 6)
        assert h["sparse_gflops"] > 0
    # G=4 random grouping realises roughly 1 - 1/G sparsity
    assert hist[0]["mask_sparsity"] == pytest.approx(0.75, abs=0.15)


def _run_forced_devices(code: str, n_devices: int):
    """Run ``code`` in a subprocess with ``n_devices`` forced CPU devices
    (the flag must be set before JAX initializes — hence a subprocess)."""
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=f"{root / 'src'}"
                   f"{os.pathsep + os.environ['PYTHONPATH'] if os.environ.get('PYTHONPATH') else ''}")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=root, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr


def test_deprecated_parallel_alias_runs_on_forced_devices():
    """tcfg.parallel (the retired pmap switch) must keep working: it now
    routes to a 1-D env-only mesh over the local devices, with a
    DeprecationWarning."""
    _run_forced_devices(
        "import warnings\n"
        "import jax, numpy as np\n"
        "assert jax.local_device_count() == 2\n"
        "from repro.marl import ic3net, train as T, envs\n"
        "cfg = ic3net.IC3NetConfig(hidden=16)\n"
        "env, ecfg = envs.make('predator_prey', n_agents=2, size=3,"
        " max_steps=6)\n"
        "tcfg = T.TrainConfig(batch=4, parallel=True)\n"
        "with warnings.catch_warnings(record=True) as w:\n"
        "    warnings.simplefilter('always')\n"
        "    _, hist = T.train(cfg, ecfg, tcfg, iterations=4, seed=0)\n"
        "assert any(issubclass(c.category, DeprecationWarning) for c in w)\n"
        "assert len(hist) == 4\n"
        "assert all(np.isfinite(h['loss']) for h in hist), hist\n",
        n_devices=2)


def _train_all_paths(cfg, ecfg, iterations, schedule=None, batch=4,
                     log_every=0):
    """(host, scan, mesh(1,1), parallel-alias) runs of one config."""
    import warnings as w
    runs = {}
    for name, tcfg, host in (
            ("host", train_mod.TrainConfig(batch=batch), True),
            ("scan", train_mod.TrainConfig(batch=batch), False),
            ("mesh", train_mod.TrainConfig(batch=batch, mesh=(1, 1)), False),
            ("alias", train_mod.TrainConfig(batch=batch, parallel=True),
             False)):
        with w.catch_warnings():
            w.simplefilter("ignore", DeprecationWarning)
            runs[name] = train_mod.train(
                cfg, ecfg, tcfg, iterations=iterations, seed=0,
                schedule=schedule, host_loop=host, log_every=log_every)
    return runs


def _assert_params_equal(pa, pb, bitwise=True):
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        if bitwise:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


def test_mesh_path_three_way_parity_dense():
    """Single device: the mesh path must train BITWISE-identically to the
    plain scan and the deprecated parallel alias (all three trace the same
    _scan_chunk), and match the host loop — the scale-out substrate cannot
    change the numbers it scales."""
    cfg = ic3net.IC3NetConfig(hidden=16)
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=6)
    runs = _train_all_paths(cfg, ecfg, iterations=5)
    _assert_params_equal(runs["scan"][0], runs["mesh"][0])
    _assert_params_equal(runs["mesh"][0], runs["alias"][0])
    _assert_params_equal(runs["host"][0], runs["mesh"][0], bitwise=False)
    np.testing.assert_allclose([h["loss"] for h in runs["host"][1]],
                               [h["loss"] for h in runs["mesh"][1]],
                               rtol=1e-4)


def test_mesh_path_three_way_parity_grouped_refresh_in_window():
    """Grouped path with a refresh_every boundary landing *inside* a scan
    window (it=3 of a 5-iteration window): the PlanState carry must
    refresh identically on the host loop, the scan and the mesh path."""
    from repro.core.schedule import SparsitySchedule
    cfg = ic3net.IC3NetConfig(hidden=16, flgw_groups=4, flgw_path="grouped")
    ecfg = env_mod.EnvConfig(n_agents=2, size=3, max_steps=6)
    sched = SparsitySchedule(groups=4, refresh_every=3)
    runs = _train_all_paths(cfg, ecfg, iterations=5, schedule=sched,
                            log_every=5)
    _assert_params_equal(runs["scan"][0], runs["mesh"][0])
    _assert_params_equal(runs["mesh"][0], runs["alias"][0])
    _assert_params_equal(runs["host"][0], runs["mesh"][0], bitwise=False)
    np.testing.assert_allclose([h["loss"] for h in runs["host"][1]],
                               [h["loss"] for h in runs["mesh"][1]],
                               rtol=1e-4)


def test_mesh_axes_actually_partition_on_forced_devices():
    """Forced 4-device host, (2 env x 2 agent) mesh: the env and agent
    constraints must produce PARTITIONED shardings (no silent full
    replication — the failure mode where a logical rule or divisibility
    drop silently replicates everything), the lowered train chunk must
    carry those shardings, and a grouped mesh run with a refresh inside
    the window must train finite."""
    _run_forced_devices(
        "import jax, jax.numpy as jnp, numpy as np\n"
        "assert jax.local_device_count() == 4\n"
        "from repro.core.schedule import SparsitySchedule\n"
        "from repro.launch.mesh import make_marl_mesh\n"
        "from repro.marl import ic3net, train as T, envs\n"
        "from repro.sharding import partition\n"
        "mesh = make_marl_mesh(env=2, agent=2)\n"
        "with mesh, partition.use_constraints(mesh):\n"
        "    ke = jax.jit(lambda x: partition.constrain(x, ('env', None)))("
        "jnp.zeros((4, 2)))\n"
        "    ag = jax.jit(lambda x: partition.constrain(x, ('agent', None)))("
        "jnp.zeros((4, 8)))\n"
        "assert not ke.sharding.is_fully_replicated, ke.sharding\n"
        "assert not ag.sharding.is_fully_replicated, ag.sharding\n"
        "assert 'env' in str(ke.sharding.spec)\n"
        "assert 'agent' in str(ag.sharding.spec)\n"
        "cfg = ic3net.IC3NetConfig(hidden=16, flgw_groups=4,"
        " flgw_path='grouped')\n"
        "env, ecfg = envs.make('predator_prey', n_agents=4, size=3,"
        " max_steps=6)\n"
        "sched = SparsitySchedule(groups=4, refresh_every=3)\n"
        "cfg2, key, params, opt = T._init(cfg, ecfg, env, seed=0)\n"
        "plans = T._encode_plans(params, cfg2)\n"
        "tcfg = T.TrainConfig(batch=4, mesh=(2, 2))\n"
        "chunk = T.make_mesh_chunk(mesh)\n"
        "with T._mesh_contexts(mesh):\n"
        "    lowered = chunk.lower(params, opt, key, plans,\n"
        "        jnp.zeros((), jnp.int32), 5, cfg2, ecfg, tcfg, env, sched)\n"
        "txt = lowered.as_text()\n"
        "import re\n"
        "axes = set(re.findall(r'sdy\\.sharding_constraint[^\\n]*\\{\"(env|agent)\"\\}',"
        " txt))\n"
        "assert axes == {'env', 'agent'}, f'unpartitioned chunk: {axes}'\n"
        "hlo = lowered.compile().as_text()\n"
        "assert 'all-reduce' in hlo, 'the partitioned chunk reduces nothing'\n"
        "_, hist = T.train(cfg, ecfg, tcfg, iterations=5, seed=0,"
        " schedule=sched, log_every=5)\n"
        "assert len(hist) == 5\n"
        "assert all(np.isfinite(h['loss']) for h in hist), hist\n",
        n_devices=4)
