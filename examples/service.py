"""The always-on permanent service: lanes, SLOs, and observability.

    PYTHONPATH=src python examples/service.py

``examples/quickstart.py`` covers the plan/execute solver; this is the
layer above it -- ``repro.serve.PermanentService``, the continuous-
batching loop that `launch/serve.py --mode permanent` (and `--soak`)
runs in production.  The lifecycle: configure lanes and budgets, warm
the compile caches, admit requests (every rejection is a typed shed,
never an exception from ``submit``), step/drain the loop, read one
metrics snapshot.
"""

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro.core.solver import SolverConfig  # noqa: E402
from repro.serve import (LaneSpec, PermanentService, ServiceConfig,  # noqa: E402
                         ShedError, enable_compile_cache,
                         start_metrics_server)

rng = np.random.default_rng(0)
# JAX_COMPILATION_CACHE_DIR when set, else the checkout's .jax_cache/
cache_dir = enable_compile_cache()

# --- 1. configure: lanes, budgets, warm-up ---------------------------------
# Two strict-priority lanes; each lane's slo_s doubles as the default
# per-request deadline.  The compile-cache dir persists XLA executables
# across process restarts; warmup_ns pre-compiles every power-of-two
# bucket geometry for n=10 so the first real bucket never retraces.
svc = PermanentService(
    SolverConfig(precision="dq_acc", backend="jnp"),
    ServiceConfig(max_batch=8,
                  lanes=(LaneSpec("interactive", 0, slo_s=2.0),
                         LaneSpec("bulk", 1, slo_s=30.0)),
                  max_queue_depth=64,
                  compile_cache_dir=cache_dir,
                  warmup_ns=(10,)))
wr = svc.warmup_report
print(f"warmup: {wr['geometries']} geometries in {wr['seconds']:.1f}s, "
      f"persistent compile cache: {wr['compile']}")

# --- 2. admit: priority lanes, typed shedding ------------------------------
# submit() returns a ticket immediately; shed tickets raise ShedError
# from result() with a typed reason (queue_full / cost_budget /
# deadline_expired / shutdown) -- load never surfaces as a bare crash.
bulk = [svc.submit(rng.uniform(-1, 1, (10, 10)), lane="bulk")
        for _ in range(6)]
urgent = svc.submit(rng.uniform(-1, 1, (10, 10)), lane="interactive")
doomed = svc.submit(rng.uniform(-1, 1, (10, 10)), lane="interactive",
                    deadline_s=0.0)          # expires before dispatch

# --- 3. the loop: continuous batching --------------------------------------
# step() dispatches one bucket whenever the device is free -- the
# interactive ticket rides the first bucket, bulk backfills its spare
# slots.  A real deployment calls step() forever; here we drain.
svc.step()
print(f"after one step: urgent done={urgent.done}, "
      f"{sum(t.done for t in bulk)}/6 bulk done (backfilled)")
svc.drain()
print(f"urgent perm = {urgent.result():+.6e}")
try:
    doomed.result()
except ShedError as e:
    print(f"doomed request shed as expected: {e}")

# --- 4. observe: one schema everywhere -------------------------------------
# The same snapshot backs the periodic log line, the soak benchmark
# gate, and the HTTP endpoint.  solver stats (cache, per-leaf device
# timings) are embedded verbatim.
snap = svc.snapshot()
req, lat = snap["requests"], snap["latency_s"]["overall"]
print(f"snapshot: admitted={req['admitted']} completed={req['completed']} "
      f"shed={req['shed']} | p50={lat['p50'] * 1e3:.0f}ms "
      f"p99={lat['p99'] * 1e3:.0f}ms | dispatches={snap['dispatches']}")
print(f"hottest kernel: "
      f"{max(snap['solver']['leaf_timings'].items(), key=lambda kv: kv[1]['total_s'])[0]}")
print(f"persistent compile cache now: {snap['compile_cache']}")

server = start_metrics_server(svc.snapshot, port=0)
import json  # noqa: E402
import urllib.request  # noqa: E402

with urllib.request.urlopen(
        f"http://127.0.0.1:{server.server_address[1]}/metrics") as r:
    print(f"GET /metrics -> schema {json.loads(r.read())['schema']}")
server.shutdown()
