"""Serve a small model through the unified ``repro.serving`` tier.

One :class:`~repro.serving.ServeSession` owns the params version, the
jitted steps and the plan policy; a :class:`~repro.serving.Engine`
schedules requests over a per-slot decode cache. Two disciplines:

* ``--mode lockstep``   — static batching: requests admit only into an
  all-free engine and the batch runs to its slowest member (the fig13
  baseline, now expressed as an admission policy).
* ``--mode continuous`` — continuous batching: requests join and leave
  the decode batch mid-flight; a freed slot takes a fresh prefill while
  its neighbours keep decoding.

On the FLGW grouped path (``--path grouped``) the session resolves the
sparse metadata (a ``PlanState``) once per params version through the
process-wide plan cache and every request shares it — the serving
analogue of the paper's encode-once OSEL dataflow. ``--plan-policy``
picks certification semantics (``certify`` | ``trust`` | ``off``).

  PYTHONPATH=src python examples/serve.py --arch gemma2_2b --batch 4 \
      --prompt-len 64 --gen 32 [--groups 4 --path grouped \
      --targets mlp,attn] [--mode continuous --requests 16 --p-arrive 0.5]
"""
import argparse

import jax
import numpy as np

from repro import compile_cache
from repro.configs import registry
from repro.core import encoder
from repro.models import transformer
from repro.serving import (Engine, Request, ServeSession, plan_cache,
                           synthetic_requests)
from repro.serving.stream import max_seq_for


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2_2b")
    ap.add_argument("--batch", type=int, default=4,
                    help="engine capacity (decode-batch slots)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--groups", type=int, default=1)
    ap.add_argument("--path", default="masked",
                    choices=("masked", "grouped"),
                    help="FLGW execution path when --groups > 1")
    ap.add_argument("--targets", default="mlp",
                    help="comma-separated FLGW targets (mlp,attn,ssm,moe)")
    ap.add_argument("--mode", default="lockstep",
                    choices=("lockstep", "continuous"))
    ap.add_argument("--plan-policy", default="certify",
                    choices=("certify", "trust", "off"))
    ap.add_argument("--requests", type=int, default=0,
                    help="continuous mode: open-loop stream size "
                         "(default 4x batch)")
    ap.add_argument("--p-arrive", type=float, default=0.5,
                    help="continuous mode: Geometric arrival probability")
    ap.add_argument("--debug-contracts", action="store_true",
                    help="run under repro.analysis.contracts.no_retrace: "
                         "fail if any jitted step recompiles mid-run")
    args = ap.parse_args(argv)

    overrides = {}
    if args.groups > 1:
        overrides = {"flgw_groups": args.groups, "flgw_path": args.path,
                     "flgw_targets": tuple(args.targets.split(","))}
    cfg = registry.get_smoke_config(args.arch, **overrides)
    key = jax.random.PRNGKey(0)
    params, _ = transformer.lm_init(key, cfg)

    session = ServeSession(cfg, params, plan_policy=args.plan_policy,
                           debug_contracts=args.debug_contracts)
    if isinstance(session.plans, encoder.PlanState):
        n_plans = sum(1 for _ in encoder.iter_flgw_layers(params))
        print(f"serving plan-aware: PlanState with {n_plans} cached "
              f"GroupPlans shared via the process plan cache "
              f"(G={cfg.flgw_groups}, targets={cfg.flgw_targets}, "
              f"plan_policy={args.plan_policy})")

    if args.mode == "lockstep":
        # fixed batch, identical shapes — the classic serve loop, expressed
        # as lockstep admission over the same engine
        prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                     (args.batch, args.prompt_len),
                                     0, cfg.vocab)
        requests = [Request(rid=i, prompt=np.asarray(prompts[i]),
                            max_new_tokens=args.gen, arrival=0)
                    for i in range(args.batch)]
    else:
        n = args.requests or 4 * args.batch
        requests = synthetic_requests(
            1, n, vocab=cfg.vocab, p_arrive=args.p_arrive,
            prompt_len=(max(1, args.prompt_len // 2), args.prompt_len),
            gen_len=(max(1, args.gen // 2), args.gen))

    engine = Engine(session, capacity=args.batch,
                    max_seq=max_seq_for(requests), admission=args.mode)
    report = engine.run(requests)

    s = report.summary()
    print(f"{args.mode}: {s['requests']} requests, "
          f"{s['generated_tokens']} tokens in {s['wall_s']:.2f}s "
          f"({s['tokens_per_s']:.1f} tok/s, "
          f"{100 * s['slot_utilization']:.0f}% slot utilization, "
          f"{report.steps} steps)")
    if s["p50_s"] is not None:
        print(f"latency: p50 {s['p50_s'] * 1e3:.0f}ms / "
              f"p99 {s['p99_s'] * 1e3:.0f}ms "
              f"(p50 {s['p50_ticks']:.0f} / p99 {s['p99_ticks']:.0f} steps)")
    pc = plan_cache.stats()
    if pc["hits"] or pc["misses"]:
        print(f"plan cache: {pc['encodes']} encode(s), {pc['hits']} hit(s) "
              f"across {s['requests']} requests")
    done = [r for r in report.records if r.completed >= 0]
    if done:
        print(f"sample generated ids (req {done[0].rid}): "
              f"{done[0].tokens[:16]}")


if __name__ == "__main__":
    main()
