"""Serving entry point: batched autoregressive generation OR the paper's
sketch-KNN service.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma_2b --reduced --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --knn --corpus-rows 4096 --queries 8
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.compat import make_mesh
from repro.core import registry
from repro.configs.base import TrainKnobs, reduced
from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_parallel
from repro.models import build_model
from repro.runtime.serve import SketchKnnService, generate


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma_2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--knn", action="store_true", help="serve sketch KNN instead")
    ap.add_argument("--corpus-rows", type=int, default=4096)
    ap.add_argument("--dims", type=int, default=4096)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--trace", action="store_true",
                    help="enable span tracing + latency histograms; dumps "
                         "the query plan and slow-query log after the KNN run")
    ap.add_argument("--estimator", default=registry.MARGIN_MLE,
                    choices=registry.names(),
                    help="distance estimator for the KNN service; the "
                         "sketch config (p, projection family) follows the "
                         "spec's declared domain")
    ap.add_argument("--p", type=float, default=None,
                    help="l_p norm order; defaults to 4 for even-p "
                         "estimators and 1.5 for fractional-p ones")
    ap.add_argument("--approx-ok", type=float, default=None, metavar="RTOL",
                    help="opt the KNN queries into the planner's approximate "
                         "contract with this relative tolerance (mle may then "
                         "ride the stacked shard fan); default keeps the "
                         "bit-exact route")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="expose /metrics (Prometheus text) and "
                         "/metrics.json on this port while serving")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency budget for the KNN queries; "
                         "routes them through the SLO front door (expired "
                         "budgets raise DeadlineExceeded, partial batches "
                         "ship early when the budget is at risk)")
    ap.add_argument("--tenant-quota", type=float, default=None,
                    metavar="ROWS_PER_S",
                    help="token-bucket admission quota (rows/second, burst "
                         "= rate) for the front door's default tenant; "
                         "over-quota requests raise Overloaded")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve KNN queries from this many replica lanes "
                         "(bit-identical answers; queries route to the "
                         "least-loaded lane)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.trace:
        obs.enable()
    if args.metrics_port is not None:
        server = obs.metrics.serve_http(args.metrics_port)
        print(f"metrics on http://{server.server_address[0]}"
              f":{server.server_address[1]}/metrics")

    if args.knn:
        from repro.core import ProjectionSpec, SketchConfig
        from repro.index import ApproxContract
        spec = registry.get(args.estimator)
        p = args.p if args.p is not None else (
            4 if spec.p_domain.contains(4) else 1.5)
        proj = ProjectionSpec()
        if proj.family not in spec.projections:
            proj = ProjectionSpec(family=spec.projections[0])
        svc = SketchKnnService(
            SketchConfig(p=p, k=128, block_d=512, projection=proj))
        approx = (ApproxContract(rtol=args.approx_ok)
                  if args.approx_ok is not None else None)
        corpus = jax.random.uniform(jax.random.key(0),
                                    (args.corpus_rows, args.dims))
        t0 = time.perf_counter()
        svc.ingest(corpus)
        t1 = time.perf_counter()
        queries = corpus[:args.queries] + 0.01 * jax.random.normal(
            jax.random.key(1), (args.queries, args.dims))
        front_door = None
        if (args.deadline_ms is not None or args.tenant_quota is not None
                or args.replicas > 1):
            from repro.serve import FrontDoor, TenantQuota
            quota = (TenantQuota(rate=args.tenant_quota,
                                 burst=args.tenant_quota)
                     if args.tenant_quota is not None else None)
            front_door = FrontDoor(svc.index, n_replicas=args.replicas,
                                   quota=quota,
                                   default_deadline_ms=args.deadline_ms)
            d, idx = front_door.query(queries, top_k=5,
                                      estimator=args.estimator,
                                      approx_ok=approx)
        else:
            d, idx = svc.query(queries, top_k=5, estimator=args.estimator,
                               approx_ok=approx)
        t2 = time.perf_counter()
        hit = float(jnp.mean((jnp.asarray(idx)[:, 0]
                              == jnp.arange(args.queries))))
        print(f"ingest {args.corpus_rows}x{args.dims}: {t1-t0:.2f}s; "
              f"query {args.queries}: {t2-t1:.2f}s; top1 self-recall {hit:.2f}")
        print("nn dists:", [round(float(x), 5) for x in d[:, 0]])
        if front_door is not None:
            sched = front_door.stats()["scheduler"]
            print(f"scheduler: admitted={sched['admitted']} "
                  f"shed={sched['shed']} "
                  f"deadline_exceeded={sched['deadline_exceeded']} "
                  f"replicas={front_door.replicas.n_replicas}")
        if args.trace:
            plan = svc.index.planner.last_plan
            if plan is not None:
                print(f"query plan: {plan.describe()}")
            dump = obs.GLOBAL_SLOW_LOG.dump()
            if dump:
                print("slow queries:")
                print(dump)
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    knobs = TrainKnobs(remat="none", sequence_parallel=False,
                       attn_q_chunk=64, ssd_chunk=32)
    ndev = len(jax.devices())
    mesh = make_mesh((ndev, 1), ("data", "model"))
    par = make_parallel(mesh, knobs=knobs, constrain=False)
    model = build_model(cfg, par, knobs)
    params = model.init(jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(2),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.perf_counter()
    out = generate(model, params, prompts, args.max_new)
    dt = time.perf_counter() - t0
    tps = args.batch * args.max_new / dt
    print(f"generated {out.shape} in {dt:.2f}s ({tps:.1f} tok/s); "
          f"sample row: {out[0, -args.max_new:].tolist()}")


if __name__ == "__main__":
    main()
