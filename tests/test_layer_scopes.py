"""Layer scopes of the MARL training step in the compiled chunk.

Every name of ``repro.scopes.LAYER_SCOPES`` has to reach the
``op_name`` metadata of some instruction of the compiled ``_train_chunk``,
where a profiler trace can find it: a scope name appears as a path
component, possibly wrapped by a transform (``jvp(vmap(rollout))``).
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.schedule import SparsitySchedule
from repro.marl import envs, ic3net
from repro.marl import train as mt
from repro.optim.optimizers import rmsprop_init
from repro.scopes import LAYER_SCOPES


def _chunk_text(groups: int, path: str, schedule) -> str:
    env = envs.get("predator_prey")
    ecfg = env.config_cls(n_agents=3, size=4, max_steps=3)
    cfg = ic3net.IC3NetConfig(hidden=16, n_agents=3, obs_dim=env.obs_dim(ecfg),
                              n_actions=env.n_actions(ecfg),
                              flgw_groups=groups, flgw_path=path)
    tcfg = mt.TrainConfig(batch=2)
    params, _ = ic3net.init(jax.random.PRNGKey(0), cfg)
    plans = ic3net.encode_plans(params, cfg)
    compiled = mt._train_chunk.lower(
        params, rmsprop_init(params), jax.random.PRNGKey(1), plans,
        jnp.asarray(0, jnp.int32), 2, cfg, ecfg, tcfg, env,
        schedule).compile()
    return compiled.as_text()


def _strip(text: str) -> str:
    """HLO text without metadata and the stack-frame tables it points to."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(.+\n)*", "\n", text)


def _has_scope(op_name: str, scope: str) -> bool:
    return re.search(rf"(^|[/(]){re.escape(scope)}($|[/)])", op_name) is not None


@pytest.mark.parametrize("groups,path,schedule", [
    (1, "dense", None),
    (4, "grouped", SparsitySchedule(groups=4, refresh_every=2)),
], ids=["dense", "grouped-g4"])
def test_every_layer_scope_reaches_the_compiled_chunk(groups, path, schedule):
    names = re.findall(r'op_name="([^"]*)"', _chunk_text(groups, path, schedule))
    grouped = path == "grouped"
    for scope in LAYER_SCOPES:
        found = any(_has_scope(n, scope) for n in names)
        if scope == "plan_refresh":
            assert found == grouped, (scope, path)
        else:
            assert found, scope
    # the backward of the policy is named too
    assert any("transpose(" in n and _has_scope(n, "policy") for n in names)


def test_scopes_change_metadata_only(monkeypatch):
    """The compiled chunk is the same program with and without the scopes.
    Both compile from empty caches: a cached jaxpr keeps the names of the
    caller that first traced it, which other tests may have been."""
    jax.clear_caches()
    scoped = _chunk_text(1, "dense", None)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    plain = _chunk_text(1, "dense", None)
    jax.clear_caches()
    assert "/policy/" in scoped and "/policy/" not in plain
    assert _strip(scoped) == _strip(plain)
