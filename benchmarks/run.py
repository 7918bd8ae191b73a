"""Benchmark harness — one benchmark per paper table/figure.

  fig7   job life cycle times (submit / full health / terminate) vs width,
         cloud-native vs legacy, and GC-vs-bulk deletion     (paper Fig. 7)
  fig8   PE-to-PE tuple throughput vs payload size           (paper Fig. 8)
  fig9   parallel-region width change latency                (paper Fig. 9)
  fig10  PE failure recovery time                            (paper Fig. 10)
  fig11  consistent-region (training) failure recovery       (paper Fig. 11)
  table1 lines-of-code accounting                            (paper Table 1)
  roofline  per-cell roofline terms from the dry-run         (EXPERIMENTS §Roofline)
  autoscale  closed-loop elasticity: reaction latency + steady width
             (paper-Fig.9-style, but the platform reacts on its own)
  transport  data-plane micro-bench: batch × payload sweep + resolve-cache
             costs vs the seed per-tuple path -> results/BENCH_transport.json
  scale_down graceful scale-down: tuples lost + drain latency with the drain
             phase on vs the seed drop-on-retire behaviour
             -> results/BENCH_scaledown.json
  teardown   job teardown: foreground cascade (owner-ref finalizers) vs
             gc_collect fixed point vs bulk label deletion (paper §8,
             Fig. 7c) -> results/BENCH_teardown.json
  oversub    the paper's §8 oversubscription pathology: the seed
             pods-per-core scheduler packs a loaded job onto one node (more
             PEs than cores -> every hosted PE slows) vs the pressure-aware
             plugin scheduler spreading it; plus a forced hot-node scenario
             where the rebalance conductor migrates PEs onto freshly added
             nodes with zero tuples lost -> results/BENCH_oversub.json
  latency    delivery-latency percentiles + pod-kill recovery span + SLO
             verdict -> results/BENCH_latency.json
  chaos      the chaos plane's (workload × fault × policy) scenario matrix:
             every FAULT_KINDS fault injected via the FaultInjection CRD,
             recovery timed by recover spans, each scenario judged into an
             SLO verdict with per-scenario seed + loss accounting
             -> results/BENCH_chaos.json
  recovery   the recovery plane head to head: cold restart (the full
             delete -> schedule -> start -> connect chain) vs warm-standby
             promotion (one epoch bump) under identical load, both timed
             by the recover span and judged by the SLO plane
             -> results/BENCH_recovery.json
  serve      paged KV-cache serving vs the fixed-slot baseline at an equal
             HBM budget: tokens/sec, TTFT p50/p99, peak admitted
             concurrency and prefix-cache hit rate over shared-prefix and
             disjoint request mixes -> results/BENCH_serve.json

``--smoke`` runs only the cheap benchmarks (CI regression guard); it fails
if the transport, scale-down, teardown or oversub bench does not produce
its JSON artifact.

Prints ``name,us_per_call,derived`` CSV rows.  Scales are reduced for the
single-core CPU container; the *shape* of each comparison (scaling with
width², cloud-native vs legacy deltas) is what reproduces the paper.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import wait_for  # noqa: E402
from repro.platform import Platform, crds  # noqa: E402
from repro.platform.legacy import LegacyPlatform  # noqa: E402

ROWS: list = []


def emit(name: str, seconds: float, derived: str = "") -> None:
    ROWS.append((name, seconds * 1e6, derived))
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def _sink_seen(p: Platform, job: str) -> int:
    """Tuples the job's sink has reported so far (0 before the first
    report) — the delivered-count probe the loss benchmarks share."""
    for pod in p.pods(job):
        if pod.status.get("sink"):
            return pod.status["sink"]["seen"]
    return 0


# ----------------------------------------------------------------- fig 7


def bench_fig7_job_lifecycle(widths=(1, 2, 3)) -> None:
    for width in widths:
        spec = {"app": {"type": "streams", "width": width,
                        "pipeline_depth": width, "source": {"rate_sleep": 0.002}}}
        # cloud native
        p = Platform(num_nodes=4)
        try:
            t0 = time.monotonic()
            p.submit("j", spec)
            assert p.wait_submitted("j", 60)
            t_sub = time.monotonic() - t0
            assert p.wait_full_health("j", 120)
            t_health = time.monotonic() - t0
            t1 = time.monotonic()
            p.delete_job("j")
            assert p.wait_terminated("j", 60)
            t_term = time.monotonic() - t1
            emit(f"fig7.cloudnative.submit.w{width}", t_sub)
            emit(f"fig7.cloudnative.fullhealth.w{width}", t_health)
            emit(f"fig7.cloudnative.terminate.w{width}", t_term,
                 "foreground cascade")
        finally:
            p.shutdown()
        # legacy (synchronous submit includes schedule+start)
        lp = LegacyPlatform(num_nodes=4)
        try:
            t0 = time.monotonic()
            lp.submit("j", spec)
            t_sub = time.monotonic() - t0
            assert wait_for(lambda: lp.full_health("j"), 120)
            t_health = time.monotonic() - t0
            t1 = time.monotonic()
            lp.cancel("j")
            t_term = time.monotonic() - t1
            emit(f"fig7.legacy.submit.w{width}", t_sub, f"zk_ops={lp.zk.ops}")
            emit(f"fig7.legacy.fullhealth.w{width}", t_health)
            emit(f"fig7.legacy.terminate.w{width}", t_term)
        finally:
            lp.shutdown()


def bench_fig7c_gc_vs_bulk(n_resources=120) -> None:
    """Kubernetes GC scaling problem (paper §8): owner-reference GC walk vs
    bulk label deletion, on the same store contents."""
    from repro.core import OwnerRef, Resource, ResourceStore

    for mode in ("gc", "bulk"):
        s = ResourceStore()
        s.create(Resource(kind="Job", name="j", labels={"j": "1"}))
        for i in range(n_resources):
            s.create(Resource(kind="Pod", name=f"p{i}", labels={"j": "1"},
                              owner_refs=(OwnerRef("Job", "j"),)))
            s.create(Resource(kind="ConfigMap", name=f"c{i}", labels={"j": "1"},
                              owner_refs=(OwnerRef("Pod", f"p{i}"),)))
        t0 = time.monotonic()
        if mode == "gc":
            s.delete("Job", "j")
            s.gc_collect()
        else:
            s.delete_collection(label_selector={"j": "1"})
        emit(f"fig7c.delete.{mode}", time.monotonic() - t0,
             f"n={2 * n_resources + 1}")


# -------------------------------------------------------------- teardown


def bench_teardown(out_path: str | None = None,
                   sizes=(30, 120, 480)) -> dict:
    """Job teardown (paper §8, Fig. 7c): foreground cascade deletion (the
    lifecycle API's happy path — owner-ref finalizers, no fixed point) vs
    the ``gc_collect`` fixed-point walk vs bulk ``delete_collection`` by
    label, on identical Job -> Pod -> ConfigMap trees.  Writes
    ``results/BENCH_teardown.json`` (``--smoke`` fails without it)."""
    from repro.core import OwnerRef, Resource, ResourceStore

    def build_tree(n):
        # a job's real shape: Job -> n Pods -> n ConfigMaps (depth 3)
        s = ResourceStore()
        s.create(Resource(kind="Job", name="j", labels={"j": "1"}))
        for i in range(n):
            s.create(Resource(kind="Pod", name=f"p{i}", labels={"j": "1"},
                              owner_refs=(OwnerRef("Job", "j"),)))
            s.create(Resource(kind="ConfigMap", name=f"c{i}",
                              labels={"j": "1"},
                              owner_refs=(OwnerRef("Pod", f"p{i}"),)))
        return s

    def build_chain(n):
        # ownership DEPTH n: each fixed-point gc round frees exactly one
        # link then rescans — the §8 pathology the cascade does not have
        s = ResourceStore()
        s.create(Resource(kind="Job", name="j", labels={"j": "1"}))
        prev = ("Job", "j")
        for i in range(n):
            s.create(Resource(kind="Link", name=f"l{i}", labels={"j": "1"},
                              owner_refs=(OwnerRef(*prev),)))
            prev = ("Link", f"l{i}")
        return s

    results = []
    for shape, build in (("tree", build_tree), ("chain", build_chain)):
        for n in sizes:
            n_objects = (2 * n + 1) if shape == "tree" else (n + 1)
            row = {"shape": shape, "n_objects": n_objects}
            for mode in ("cascade", "gc", "bulk"):
                s = build(n)
                t0 = time.monotonic()
                if mode == "cascade":
                    s.delete("Job", "j", propagation="foreground")
                elif mode == "gc":
                    s.delete("Job", "j")
                    s.gc_collect()
                else:
                    s.delete_collection(label_selector={"j": "1"})
                dt = time.monotonic() - t0
                leftovers = len(s.list(label_selector={"j": "1"}))
                assert leftovers == 0, f"{mode} left {leftovers} objects"
                assert s.gc_runs == (1 if mode == "gc" else 0)
                row[mode] = {"seconds": dt,
                             "us_per_object": dt / n_objects * 1e6}
                emit(f"teardown.{shape}.{mode}.n{n_objects}", dt,
                     f"{dt / n_objects * 1e6:.1f}us/obj")
            row["cascade_vs_gc"] = (row["gc"]["seconds"] /
                                    max(row["cascade"]["seconds"], 1e-9))
            results.append(row)
    deep = results[-1]  # largest chain: the fixed point's worst case
    tree = [r for r in results if r["shape"] == "tree"][-1]
    report = {"benchmark": "teardown", "results": results,
              "chain_cascade_vs_gc_speedup": deep["cascade_vs_gc"],
              "tree_cascade_vs_gc_speedup": tree["cascade_vs_gc"],
              "tree_cascade_us_per_object": tree["cascade"]["us_per_object"],
              "tree_bulk_us_per_object": tree["bulk"]["us_per_object"]}
    out = out_path or os.path.join(os.path.dirname(__file__), "..",
                                   "results", "BENCH_teardown.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    emit("teardown.chain.cascade_vs_gc", 0.0,
         f"{report['chain_cascade_vs_gc_speedup']:.1f}x")
    return report


# ----------------------------------------------------------------- fig 8


def _pump_tuple_queue(payload: int, batch: int, n: int) -> float:
    """Producer/consumer pair over one TupleQueue; returns elapsed seconds.
    ``batch == 1`` is the per-tuple path (put/get), larger batches use
    ``put_many``/``get_many`` (one lock crossing per batch)."""
    import threading

    from repro.platform.fabric import TupleQueue

    blob = bytes(payload)
    q = TupleQueue(maxsize=4096)

    def consume():
        got = 0
        while got < n:
            if batch == 1:
                if q.get(timeout=1.0) is not None:
                    got += 1
            else:
                got += len(q.get_many(batch, timeout=1.0))

    # daemon: a producer failure must fail the bench, not hang CI on join
    th = threading.Thread(target=consume, daemon=True)
    th.start()
    t0 = time.monotonic()
    if batch == 1:
        for i in range(n):
            q.put({"seq": i, "payload": blob})
    else:
        buf = []
        for i in range(n):
            buf.append({"seq": i, "payload": blob})
            if len(buf) >= batch:
                q.put_many(buf)
                buf = []
        if buf:
            q.put_many(buf)
    _join_or_fail(th)
    return time.monotonic() - t0


def _join_or_fail(th, timeout: float = 60.0) -> None:
    """A consumer shortfall (lost/short-counted tuples) must fail the bench
    promptly, not hang CI until the job timeout."""
    th.join(timeout)
    if th.is_alive():
        raise RuntimeError("transport bench consumer stalled "
                           "(tuples lost or short-counted)")


def _pump_seed_queue(payload: int, n: int) -> float:
    """The seed data plane for reference: one ``queue.Queue`` put/get per
    tuple — what the ≥5× batched-speedup acceptance is measured against."""
    import queue as pyqueue
    import threading

    blob = bytes(payload)
    q = pyqueue.Queue(maxsize=4096)

    def consume():
        got = 0
        while got < n:
            try:
                q.get(timeout=1.0)
                got += 1
            except pyqueue.Empty:
                pass

    th = threading.Thread(target=consume, daemon=True)
    th.start()
    t0 = time.monotonic()
    for i in range(n):
        q.put({"seq": i, "payload": blob})
    _join_or_fail(th)
    return time.monotonic() - t0


def _bench_resolve(n: int = 50000, uncached: bool = True) -> tuple:
    """(per-send ``resolve``, cached ``EndpointCache.get``) µs per call —
    the control-path cost the data path no longer pays per tuple.  Pass
    ``uncached=False`` to skip the per-send loop (first element is None)."""
    from repro.platform.fabric import EndpointCache, Fabric, TupleQueue

    fab = Fabric()
    fab.publish("job", 1, 0, TupleQueue())
    per_send_us = None
    if uncached:
        t0 = time.monotonic()
        for _ in range(n):
            fab.resolve("job", 1, 0)
        per_send_us = (time.monotonic() - t0) / n * 1e6
    cache = EndpointCache(fab)
    cache.get("job", 1, 0)
    t0 = time.monotonic()
    for _ in range(n):
        cache.get("job", 1, 0)
    cached_us = (time.monotonic() - t0) / n * 1e6
    return per_send_us, cached_us


def bench_fig8_pe_throughput(payloads=(1, 64, 1024, 65536)) -> None:
    """Two PEs, tuples with varying payload bytes; tuples/sec through the
    fabric — per-tuple and batched paths — plus the name-resolution (DNS)
    latency the paper highlights, uncached vs the sender EndpointCache."""
    from repro.platform.fabric import Fabric, TupleQueue

    for payload in payloads:
        n = 20000 if payload <= 1024 else 4000
        dt = _pump_tuple_queue(payload, 1, n)
        emit(f"fig8.queue.p{payload}", dt / n, f"{n / dt:.0f} tuples/s")
        for batch in (64, 256):
            dt = _pump_tuple_queue(payload, batch, n)
            emit(f"fig8.queue_batched.b{batch}.p{payload}", dt / n,
                 f"{n / dt:.0f} tuples/s")
    # name resolution with propagation delay (paper §8 networking latency)
    for delay in (0.0, 0.01):
        fab = Fabric(dns_delay=delay)
        q2 = TupleQueue()
        fab.publish("job", 1, 0, q2)
        t0 = time.monotonic()
        fab.resolve("job", 1, 0)
        emit(f"fig8.resolve.dns{int(delay * 1000)}ms", time.monotonic() - t0)
    # cached resolution: what every send after the first costs (smaller n,
    # cached side only — the full sweep belongs to the transport bench)
    _, cached_us = _bench_resolve(n=20000, uncached=False)
    emit("fig8.resolve.cached", cached_us / 1e6)


# -------------------------------------------------------------- transport


def bench_transport(out_path: str | None = None) -> dict:
    """Transport micro-bench: batch-size × payload sweep through the
    TupleQueue ring plus resolve-path costs, against the seed per-tuple
    ``queue.Queue`` baseline.  Writes machine-readable
    ``results/BENCH_transport.json`` — the perf trajectory CI accumulates
    (``--smoke`` fails if the file is not produced)."""
    payloads = (1, 1024)
    batches = (1, 16, 64, 256)
    results = []
    for payload in payloads:
        n = 40000 if payload == 1 else 10000
        dt = _pump_seed_queue(payload, n)
        seed_tps = n / dt
        results.append({"path": "seed_queue", "payload": payload, "batch": 1,
                        "tuples_per_sec": seed_tps, "us_per_tuple": dt / n * 1e6})
        emit(f"transport.seed.p{payload}", dt / n, f"{seed_tps:.0f} tuples/s")
        for batch in batches:
            dt = _pump_tuple_queue(payload, batch, n)
            tps = n / dt
            results.append({"path": "tuple_queue", "payload": payload,
                            "batch": batch, "tuples_per_sec": tps,
                            "us_per_tuple": dt / n * 1e6,
                            "speedup_vs_seed": tps / seed_tps})
            emit(f"transport.batch{batch}.p{payload}", dt / n,
                 f"{tps:.0f} tuples/s;{tps / seed_tps:.1f}x seed")
    # resolve path: per-send re-resolve (seed behaviour) vs cached
    uncached_us, cached_us = _bench_resolve()
    emit("transport.resolve.per_send", uncached_us / 1e6)
    emit("transport.resolve.cached", cached_us / 1e6)

    small = [r for r in results
             if r["payload"] == 1 and r["path"] == "tuple_queue"]
    seed_small = next(r for r in results
                      if r["payload"] == 1 and r["path"] == "seed_queue")
    single = next(r for r in small if r["batch"] == 1)
    best = max(small, key=lambda r: r["tuples_per_sec"])
    report = {
        "benchmark": "transport",
        "results": results,
        "resolve": {"per_send_us": uncached_us, "cached_us": cached_us},
        "seed_single_tuple_tps": seed_small["tuples_per_sec"],
        "single_tuple_tps": single["tuples_per_sec"],
        "batched_tps": best["tuples_per_sec"],
        "batched_best_batch": best["batch"],
        "speedup_batched_vs_seed": best["tuples_per_sec"] / seed_small["tuples_per_sec"],
        "speedup_batched_vs_single": best["tuples_per_sec"] / single["tuples_per_sec"],
    }
    out = out_path or os.path.join(os.path.dirname(__file__), "..", "results",
                                   "BENCH_transport.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    emit("transport.speedup_batched_vs_seed", 0.0,
         f"{report['speedup_batched_vs_seed']:.1f}x")
    return report


# ------------------------------------------------------------- scale_down


def bench_scaledown(out_path: str | None = None, n_tuples: int = 600) -> dict:
    """Graceful scale-down vs the seed drop behaviour.

    A loaded streams job (finite source, channels slower than the source so
    the region's rings hold a backlog) is scaled 2 -> 1 mid-stream.  With
    ``drain`` enabled the retiring channels pull their rings dry and the
    sink sees every tuple; with ``drain: false`` (the seed behaviour) the
    in-flight backlog of the retired channels is dropped.  Records tuples
    lost and the drain latency (width edit -> retired pods gone) for both
    modes into ``results/BENCH_scaledown.json``.
    """
    modes = {}
    for label, drain in (("drain", {"timeout": 15.0, "grace": 0.3}),
                         ("drop", False)):
        # emit_batch_max bounded so the pull batch a stopping PE has in hand
        # stays small: the loss measured is the *ring* backlog, not an
        # artifact of how much work was mid-flight; report_every=10 keeps
        # the sink count quantization well under the losses measured
        spec = {"app": {"type": "streams", "width": 2, "pipeline_depth": 2,
                        "source": {"tuples": n_tuples, "rate_sleep": 0.0002},
                        "channel": {"work_sleep": 0.002,
                                    "emit_batch": 16, "emit_batch_max": 32},
                        "sink": {"report_every": 10}},
                "drain": drain}
        p = Platform(num_nodes=4)
        try:
            p.submit("j", spec)
            assert p.wait_full_health("j", 120)

            def sink_seen():
                return _sink_seen(p, "j")

            assert wait_for(lambda: sink_seen() > 50, 60)
            n0 = len(p.pods("j"))
            t0 = time.monotonic()
            p.set_width("j", "par", 1)
            assert wait_for(lambda: len(p.pods("j")) == n0 - 2, 60)
            retired_s = time.monotonic() - t0
            # quiesce: the sink count stops moving (source finite)
            last = [-1, time.monotonic()]

            def quiesced():
                seen = sink_seen()
                if seen != last[0]:
                    last[0] = seen
                    last[1] = time.monotonic()
                return seen >= n_tuples or time.monotonic() - last[1] > 2.0
            wait_for(quiesced, 90)
            seen = sink_seen()
            dropped = p.job_metrics("j").get("tuplesDropped", 0)
            modes[label] = {"emitted": n_tuples, "delivered": seen,
                            "lost": n_tuples - seen,
                            "metricsDropped": dropped,
                            "drain_latency_s": retired_s}
            emit(f"scaledown.{label}.lost", 0.0,
                 f"{n_tuples - seen} of {n_tuples}")
            emit(f"scaledown.{label}.retire_latency", retired_s)
        finally:
            p.shutdown()
    report = {"benchmark": "scale_down", "modes": modes,
              "zero_loss_with_drain": modes["drain"]["lost"] == 0}
    out = out_path or os.path.join(os.path.dirname(__file__), "..", "results",
                                   "BENCH_scaledown.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    emit("scaledown.zero_loss_with_drain", 0.0,
         str(report["zero_loss_with_drain"]))
    return report


# --------------------------------------------------------------- scaleout


def bench_scaleout(out_path: str | None = None, n_tuples: int = 600) -> dict:
    """Cross-process scale-out: end-to-end throughput of a sleep-bound
    streams job as channel width grows 1 -> 2 -> 4, with every PE hosted
    in a per-node worker OS process (``process_isolation=True``) so tuple
    batches cross real length-prefixed socket frames.  Two payload sizes
    exercise the wire codec's small-frame and bulk paths.

    Each channel sleeps ``work_sleep`` per tuple, so aggregate service
    rate — not core count — bounds throughput and the sweep measures
    pipeline parallelism across worker processes honestly even on a
    single-core runner.  One Platform (and its four spawned workers) is
    reused across all six rows; a warmup job pays the fork + handshake
    cost once, outside the measurement.

    Writes ``results/BENCH_scaleout.json`` (``--smoke`` fails without
    it); the headline is ``scaling_1_to_4`` at the large-payload row,
    with 1.5x as the acceptance floor.
    """
    p = Platform(num_nodes=4, process_isolation=True)
    rows = []
    try:
        # warmup: touch all four nodes once so no sweep row pays the
        # worker-process spawn + handshake cost
        p.submit("warm", {"app": {"type": "streams", "width": 4,
                                  "pipeline_depth": 1,
                                  "source": {"tuples": 50}}})
        assert wait_for(lambda: _sink_seen(p, "warm") >= 50, 60)
        p.delete_job("warm")
        assert p.wait_terminated("warm", 30)
        assert p.rest.workers, "no worker processes spawned"
        for payload in (64, 4096):
            for width in (1, 2, 4):
                job = f"so-w{width}-p{payload}"
                spec = {"app": {"type": "streams", "width": width,
                                "pipeline_depth": 1,
                                "source": {"tuples": n_tuples,
                                           "rate_sleep": 0.0,
                                           "payload_bytes": payload},
                                "channel": {"work_sleep": 0.004},
                                "sink": {"report_every": 25}}}
                t0 = time.monotonic()
                p.submit(job, spec)
                assert wait_for(
                    lambda j=job: _sink_seen(p, j) >= n_tuples, 120)
                dt = time.monotonic() - t0
                tps = n_tuples / dt
                rows.append({"workers": width, "payload": payload,
                             "tuples": n_tuples, "seconds": dt,
                             "tuples_per_sec": tps})
                emit(f"scaleout.w{width}.p{payload}", dt / n_tuples,
                     f"{tps:.0f} tuples/s")
                p.delete_job(job)
                assert p.wait_terminated(job, 30)
    finally:
        p.shutdown()

    def tps(width: int, payload: int) -> float:
        return next(r["tuples_per_sec"] for r in rows
                    if r["workers"] == width and r["payload"] == payload)

    scaling = {f"p{pl}": tps(4, pl) / tps(1, pl) for pl in (64, 4096)}
    report = {"benchmark": "scaleout", "rows": rows,
              "scaling_1_to_4": scaling,
              "meets_floor": scaling["p4096"] >= 1.5}
    out = out_path or os.path.join(os.path.dirname(__file__), "..", "results",
                                   "BENCH_scaleout.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    emit("scaleout.scaling_1_to_4", 0.0,
         f"p64={scaling['p64']:.2f}x;p4096={scaling['p4096']:.2f}x")
    return report


# --------------------------------------------------------------- oversub


def _oversub_run(profile: str, n_tuples: int) -> dict:
    """One scheduling run of the packed-vs-spread comparison: a cluster of
    small nodes where three carry idle 'ballast' pods (static placement
    noise), a loaded job submitted on top, and the kubelet's CPU model on —
    a node hosting more running PEs than cores slows every one of them.

    The seed pods-per-core load factor counts the idle ballast as load and
    the heavy channels as no more than a sink, so it packs the whole job
    onto the ballast-free node (more PEs than cores — the §8 pathology);
    the pressure-aware profile accounts requested cores and live pressure
    and spreads.  Returns per-channel throughput stats + completion time.
    """
    from repro.core import Resource

    p = Platform(num_nodes=4, cores_per_node=2, scheduler_profile=profile,
                 cpu_model=True)
    try:
        # ballast: four idle pinned pods per node1..3 (never Running — pure
        # bookkeeping noise for the load factor; tiny resource requests).
        # The seed pods/cores count mistakes them for load and funnels the
        # whole job onto the ballast-free node0; the pressure profile
        # accounts their 0.1-core requests for what they are and spreads
        # the 1.0-core channels.
        for node_i in (1, 2, 3):
            for j in range(4):
                p.store.create(Resource(
                    kind=crds.POD, name=f"ballast-{node_i}-{j}",
                    spec={"job": "ballast", "peId": 100 + node_i * 10 + j,
                          "pod_spec": {"nodeName": f"node{node_i}",
                                       "resources": {"cores": 0.1}}},
                    status={"phase": "Pending"}))
        # flooding source + 20 ms/tuple channels: the region is the
        # bottleneck by construction, and 20 ms sleeps sit far above the
        # container's sleep-granularity floor, so a packed channel's
        # stretched work_sleep (share < 1) shows directly in its delivered
        # rate and in the stream's completion time (sleeping threads also
        # do not contend for real host CPU — the model, not the host, is
        # what's measured)
        spec = {"app": {"type": "streams", "width": 4, "pipeline_depth": 1,
                        "pre_ops": 0, "post_ops": 0,
                        "source": {"tuples": n_tuples, "rate_sleep": 0.0},
                        "channel": {"work_sleep": 0.02,
                                    "placement": {"cores": 1.0},
                                    "emit_batch": 16, "emit_batch_max": 32},
                        "sink": {"report_every": 10}}}
        p.submit("j", spec)
        assert p.wait_full_health("j", 120)

        def sink_seen():
            return _sink_seen(p, "j")

        # time the stream, not the control plane: first sink report ->
        # complete; sample per-channel rates mid-stream (live window)
        assert wait_for(lambda: sink_seen() > 0, 60)
        t0 = time.monotonic()
        assert wait_for(lambda: sink_seen() >= n_tuples // 2, 180)
        ops = p.job_metrics("j").get("operators", {})
        rates = {name: entry.get("rate", 0.0)
                 for name, entry in ops.items() if name.startswith("ch")}
        assert wait_for(lambda: sink_seen() >= n_tuples, 180), \
            f"{profile}: sink saw {sink_seen()}/{n_tuples}"
        completion_s = time.monotonic() - t0
        placement: dict = {}
        for pod in p.pods("j"):
            node = pod.spec.get("nodeName")
            placement.setdefault(node, []).append(pod.spec["peId"])
        packed_max = max(len(v) for v in placement.values())
        live = [r for r in rates.values() if r > 0]
        return {"profile": profile, "completion_s": completion_s,
                "channels_per_node_max": packed_max,
                "placement": {k: sorted(v) for k, v in placement.items()},
                "channel_rates": rates,
                "mean_channel_rate": sum(live) / len(live) if live else 0.0,
                "min_channel_rate": min(live) if live else 0.0}
    finally:
        p.shutdown()


def _oversub_rebalance(n_tuples: int) -> dict:
    """Forced hot node -> zero-loss rebalance: a loaded job lands on a
    single 2-core node (nowhere else to go — podsPerCore far past 1), then
    capacity is added.  The pressure plane marks the node hot, the
    rebalance conductor migrates region PEs onto the new nodes through the
    loss-proofed restart chain, and the finite stream still arrives
    complete at the sink."""
    p = Platform(num_nodes=1, cores_per_node=2, scheduler_profile="pressure",
                 cpu_model=True, rebalance=True, pressure_interval=0.2)
    p.rebalancer.sustain_s = 0.5
    p.rebalancer.cooldown = 1.0
    try:
        spec = {"app": {"type": "streams", "width": 2, "pipeline_depth": 1,
                        "source": {"tuples": n_tuples, "rate_sleep": 0.002},
                        "channel": {"work_sleep": 0.002,
                                    "emit_batch": 16, "emit_batch_max": 32},
                        "sink": {"report_every": 10}}}
        p.submit("j", spec)
        assert p.wait_full_health("j", 120)
        assert wait_for(  # the lone node is marked hot by the heartbeat
            lambda: (p.node_pressure("node0").get("podsPerCore", 0) >= 1.0),
            30)
        # relief capacity arrives; the conductor should start migrating
        t0 = time.monotonic()
        p.add_node("relief0", 8)
        p.add_node("relief1", 8)
        assert wait_for(lambda: p.rebalancer.migrations >= 1, 60), \
            "no rebalance migration despite sustained hot node + cold capacity"
        first_migration_s = time.monotonic() - t0

        def sink_seen():
            return _sink_seen(p, "j")

        assert wait_for(lambda: sink_seen() >= n_tuples, 240), \
            f"rebalance lost tuples: sink saw {sink_seen()}/{n_tuples}"
        moved = [pod.spec["peId"] for pod in p.pods("j")
                 if pod.spec.get("nodeName", "").startswith("relief")]
        return {"migrations": p.rebalancer.migrations,
                "first_migration_s": first_migration_s,
                "emitted": n_tuples, "delivered": sink_seen(),
                "lost": n_tuples - sink_seen(),
                "pes_on_relief_nodes": sorted(moved),
                "dropped_in_metrics": p.job_metrics("j").get("tuplesDropped",
                                                             0)}
    finally:
        p.shutdown()


def bench_oversub(out_path: str | None = None, n_tuples: int = 800,
                  rebalance_tuples: int = 600) -> dict:
    """Oversubscription pathology (paper §8) + zero-loss rebalance.

    Writes ``results/BENCH_oversub.json`` (``--smoke`` fails without it):
    the pressure-aware scheduler must keep per-PE throughput degradation
    on the oversubscribed topology measurably lower than the seed
    load-factor scheduler, and the forced hot-node scenario must migrate
    with zero tuples lost."""
    runs = {prof: _oversub_run(prof, n_tuples)
            for prof in ("seed", "pressure")}
    for prof, run in runs.items():
        emit(f"oversub.{prof}.completion", run["completion_s"],
             f"channels_per_node_max={run['channels_per_node_max']}")
        emit(f"oversub.{prof}.mean_channel_rate", 0.0,
             f"{run['mean_channel_rate']:.0f} tuples/s "
             f"(min {run['min_channel_rate']:.0f})")
    # per-PE throughput degradation: how much of the spread placement's
    # per-channel rate each profile loses (0 = none).  The best observed
    # mean is the un-oversubscribed reference.
    best = max(run["mean_channel_rate"] for run in runs.values()) or 1e-9
    degradation = {prof: 1.0 - run["mean_channel_rate"] / best
                   for prof, run in runs.items()}
    rebalance = _oversub_rebalance(rebalance_tuples)
    emit("oversub.rebalance.migrations", 0.0,
         str(rebalance["migrations"]))
    emit("oversub.rebalance.lost", 0.0,
         f"{rebalance['lost']} of {rebalance['emitted']}")
    report = {
        "benchmark": "oversub",
        "packed_vs_spread": runs,
        "degradation": degradation,
        "pressure_degrades_less":
            degradation["pressure"] < degradation["seed"],
        "per_pe_rate_ratio_pressure_vs_seed":
            runs["pressure"]["mean_channel_rate"]
            / max(runs["seed"]["mean_channel_rate"], 1e-9),
        "completion_ratio_seed_vs_pressure":
            runs["seed"]["completion_s"] / runs["pressure"]["completion_s"],
        "seed_packed_more": (runs["seed"]["channels_per_node_max"]
                             > runs["pressure"]["channels_per_node_max"]),
        "rebalance": rebalance,
        "rebalance_zero_loss": rebalance["lost"] == 0,
    }
    out = out_path or os.path.join(os.path.dirname(__file__), "..", "results",
                                   "BENCH_oversub.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    emit("oversub.completion_ratio", 0.0,
         f"{report['completion_ratio_seed_vs_pressure']:.2f}x "
         f"(seed vs pressure)")
    return report


# ----------------------------------------------------------------- fig 9


def bench_fig9_width_change(widths=(1, 2, 3)) -> None:
    for width in widths:
        spec = {"app": {"type": "streams", "width": width,
                        "pipeline_depth": width, "source": {"rate_sleep": 0.002}}}
        p = Platform(num_nodes=4)
        try:
            p.submit("j", spec)
            assert p.wait_full_health("j", 120)
            n0 = len(p.pods("j"))
            t0 = time.monotonic()
            p.set_width("j", "par", 2 * width)
            assert wait_for(lambda: len(p.pods("j")) == n0 + width * width
                            and p.job_status("j").get("fullHealth"), 120)
            emit(f"fig9.cloudnative.double.w{width}", time.monotonic() - t0)
            t0 = time.monotonic()
            p.set_width("j", "par", width)
            assert wait_for(lambda: len(p.pods("j")) == n0, 120)
            emit(f"fig9.cloudnative.halve.w{2 * width}", time.monotonic() - t0)
        finally:
            p.shutdown()
        lp = LegacyPlatform(num_nodes=4)
        try:
            lp.submit("j", spec)
            assert wait_for(lambda: lp.full_health("j"), 120)
            t0 = time.monotonic()
            lp.change_width("j", "par", 2 * width)  # sequential stop->start
            assert wait_for(lambda: lp.full_health("j"), 120)
            emit(f"fig9.legacy.double.w{width}", time.monotonic() - t0)
        finally:
            lp.cancel("j")
            lp.shutdown()


# ---------------------------------------------------------------- fig 10


def bench_fig10_pe_failure_recovery(widths=(2, 3)) -> None:
    for width in widths:
        spec = {"app": {"type": "streams", "width": width,
                        "pipeline_depth": width, "source": {"rate_sleep": 0.002}}}
        p = Platform(num_nodes=4)
        try:
            p.submit("j", spec)
            assert p.wait_full_health("j", 120)
            n_pes = len(p.pods("j"))
            for victim in (1, n_pes // 2):
                t0 = time.monotonic()
                p.kill_pod("j", victim)
                wait_for(lambda: not p.job_status("j").get("fullHealth"), 20)
                assert p.wait_full_health("j", 120)
                emit(f"fig10.recovery.pes{n_pes}.pe{victim}",
                     time.monotonic() - t0)
        finally:
            p.shutdown()


# ---------------------------------------------------------------- fig 11


def bench_fig11_cr_recovery(tmpdir="/tmp/repro-bench-ckpt") -> None:
    spec = {
        "app": {"type": "train", "arch": "gemma-2b", "data_parallel": 2,
                "steps": 1000, "batch_per_shard": 2, "seq_len": 32},
        "consistentRegion": {"name": "dp", "interval": 5},
    }
    p = Platform(num_nodes=4, ckpt_root=tmpdir)
    try:
        p.submit("t", spec)
        assert p.wait_full_health("t", 180)
        assert p.wait_cr_committed("t", "dp", 5, 300)
        trainer_pes = [x.spec["peId"] for x in p.store.list(crds.PE, "default")
                       if "trainer" in str(x.spec.get("operators"))]
        for victim in trainer_pes[:2]:
            before = p.rest.get_cr_state("t", "dp")["lastCommitted"]
            t0 = time.monotonic()
            p.kill_pod("t", victim)
            assert p.wait_cr_committed("t", "dp", before + 5, 300)
            emit(f"fig11.cr_recovery.pe{victim}", time.monotonic() - t0,
                 f"rollback_to={before}")
    finally:
        p.delete_job("t")
        p.wait_terminated("t", 30)
        p.shutdown()


# ------------------------------------------------------------- autoscale


def bench_autoscale_rampup(max_width: int = 4, settle: float = 3.0) -> None:
    """Closed-loop elasticity (the self-driving version of Fig. 9): a width-1
    region under a source that outruns its channels; measure the latency from
    policy creation to the conductor's first width change, to the pods
    existing, and to full health — then the steady-state width it settles at."""
    spec = {"app": {"type": "streams", "width": 1, "pipeline_depth": 1,
                    "source": {"rate_sleep": 0.0005},
                    "channel": {"work_sleep": 0.004}}}
    p = Platform(num_nodes=4)
    try:
        p.submit("j", spec)
        assert p.wait_full_health("j", 120)
        n0 = len(p.pods("j"))
        t0 = time.monotonic()
        p.set_scaling_policy("j", "par", max_width=max_width, scale_up_at=0.3,
                             cooldown=0.5)
        assert wait_for(lambda: p.region_width("j", "par") >= 2, 120)
        emit("autoscale.reaction.width", time.monotonic() - t0,
             "policy -> first width change")
        assert wait_for(lambda: len(p.pods("j")) >= n0 + 1, 120)
        emit("autoscale.reaction.pods", time.monotonic() - t0,
             "policy -> scaled pods exist")
        assert p.wait_full_health("j", 120)
        emit("autoscale.reaction.fullhealth", time.monotonic() - t0)
        time.sleep(settle)  # let further scale steps land
        width = p.region_width("j", "par")
        bp = p.job_metrics("j").get("regions", {}).get("par", {}).get(
            "backpressure", -1.0)
        emit("autoscale.steady.width", 0.0,
             f"width={width};backpressure={bp:.2f};max={max_width}")
    finally:
        p.shutdown()


# ---------------------------------------------------------------- table 1


def bench_table1_loc() -> None:
    """Physical LoC accounting (paper Table 1): how small the platform is
    relative to the substrate it manages."""
    root = os.path.join(os.path.dirname(__file__), "..")
    buckets = {
        "platform(core+platform)": ["src/repro/core", "src/repro/platform"],
        "substrate(models+train+serve+data)": [
            "src/repro/models", "src/repro/train", "src/repro/serve",
            "src/repro/data", "src/repro/sharding", "src/repro/ckpt"],
        "kernels": ["src/repro/kernels"],
        "launch+configs": ["src/repro/launch", "src/repro/configs"],
        "tests+benchmarks": ["tests", "benchmarks"],
    }
    total = 0
    for name, dirs in buckets.items():
        n = 0
        for d in dirs:
            for dirpath, _, files in os.walk(os.path.join(root, d)):
                for f in files:
                    if f.endswith(".py"):
                        with open(os.path.join(dirpath, f), errors="ignore") as fh:
                            n += sum(1 for line in fh
                                     if line.strip() and not line.strip().startswith("#"))
        total += n
        emit(f"table1.loc.{name}", 0.0, str(n))
    emit("table1.loc.total", 0.0, str(total))


# --------------------------------------------------------------- roofline


def bench_roofline() -> None:
    path = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun.json")
    if not os.path.exists(path):
        print("roofline: results/dryrun.json missing — run "
              "`python -m repro.launch.dryrun --all --both-meshes` first",
              flush=True)
        return
    with open(path) as f:
        recs = json.load(f)
    for r in sorted(recs, key=lambda x: (x["arch"], x["shape"], x["mesh"])):
        if r.get("status") != "ok":
            continue
        t = r["roofline"]
        name = f"roofline.{r['arch']}.{r['shape']}.{r['mesh']}"
        step = max(t["compute_s"], t["memory_s"], t["collective_s"])
        emit(name, step,
             f"dom={t['dominant']};frac={t['roofline_fraction_compute']:.2f};"
             f"useful={t['model_vs_hlo_flops']:.2f}")


def bench_latency(out_path: str | None = None, n_tuples: int = 900) -> dict:
    """Observability plane end to end: delivery-latency percentiles under
    load, a pod-kill recovery-time span, and an SLO verdict over the run.

    A finite-source streams job runs with a 2-wide channel region; sources
    stamp ingest watermarks, the sink's P² digests estimate delivery
    percentiles, and the metrics plane publishes them per job/region.  Mid
    stream one channel pod is killed: the span tracer times the recovery
    chain (failure -> recreate -> bind -> start -> connected) and an ``SLO``
    resource (p95 target, loss budget, recovery bound) is judged into a
    Met/Violated verdict with an error-budget ledger.  Writes
    ``results/BENCH_latency.json`` plus a Chrome trace export of the run's
    span trees.
    """
    spec = {"app": {"type": "streams", "width": 2, "pipeline_depth": 1,
                    "source": {"tuples": n_tuples, "rate_sleep": 0.001},
                    "channel": {"work_sleep": 0.0005,
                                "emit_batch": 16, "emit_batch_max": 32},
                    "sink": {"report_every": 10}},
            "drain": {"timeout": 15.0, "grace": 0.3}}
    slo_spec = {"latency_p95_ms": 500.0, "loss_budget": 64,
                "recovery_time_s": 30.0}
    p = Platform(num_nodes=4)
    try:
        p.submit("j", spec)
        assert p.wait_full_health("j", 120)
        p.set_slo("j", **slo_spec)

        def sink_seen():
            return _sink_seen(p, "j")

        assert wait_for(lambda: sink_seen() > 150, 60)
        # mid-stream chaos: kill one channel pod, time the recovery
        t0 = time.monotonic()
        p.kill_pod("j", 1)
        wait_for(lambda: not p.job_status("j").get("fullHealth"), 20)
        assert p.wait_full_health("j", 120)
        recovery_wall_s = time.monotonic() - t0
        # quiesce: the finite source completes and the sink count stops
        last = [-1, time.monotonic()]

        def quiesced():
            seen = sink_seen()
            if seen != last[0]:
                last[0] = seen
                last[1] = time.monotonic()
            return seen >= n_tuples or time.monotonic() - last[1] > 2.0
        wait_for(quiesced, 120)
        seen = sink_seen()
        assert wait_for(
            lambda: p.slo_status("j").get("ledger", {}).get("evaluations", 0) > 0,
            30)
        m = p.job_metrics("j")
        latency = {k: m.get(k) for k in
                   ("latencyP50", "latencyP95", "latencyP99",
                    "latencyMax", "latencySamples")}
        recs = [s for s in p.trace.spans(name="recover")
                if s.attrs.get("job") == "j" and s.t1 is not None]
        recovery_span_s = max(s.t1 - s.t0 for s in recs) if recs else None
        recovery_chain = p.trace.render(recs[-1]) if recs else ""
        slo = p.slo_status("j")
        verdicts = {c["type"]: c["status"]
                    for c in slo.get("conditions", ())
                    if c["type"] in ("Met", "Violated")}
        results_dir = os.path.join(os.path.dirname(__file__), "..", "results")
        os.makedirs(results_dir, exist_ok=True)
        trace_path = os.path.join(results_dir, "BENCH_latency_trace.json")
        p.export_trace(trace_path)
        report = {
            "benchmark": "latency",
            "emitted": n_tuples, "delivered": seen,
            "lost": n_tuples - seen,
            "metricsDropped": m.get("tuplesDropped", 0),
            "latency_ms": latency,
            "recovery": {"wall_s": round(recovery_wall_s, 4),
                         "span_s": round(recovery_span_s, 4)
                         if recovery_span_s is not None else None,
                         "spans": len(recs),
                         "chain": recovery_chain.splitlines()},
            "slo": {"spec": slo_spec, "verdicts": verdicts,
                    "ledger": slo.get("ledger", {})},
            "trace_export": os.path.basename(trace_path),
            "prometheus_sample": p.metrics_text().splitlines()[:12],
        }
    finally:
        p.shutdown()
    out = out_path or os.path.join(os.path.dirname(__file__), "..", "results",
                                   "BENCH_latency.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    emit("latency.p95_ms", 0.0, str(latency.get("latencyP95")))
    emit("latency.recovery_span", recovery_span_s or 0.0,
         f"wall={recovery_wall_s:.2f}s")
    emit("latency.slo_verdict", 0.0,
         "Met" if verdicts.get("Met") == "True" else "Violated")
    return report


# ------------------------------------------------------------------ chaos


#: The chaos matrix's workloads: one long-lived rate-limited job per shape.
#: ``steady`` opts into the straggler monitor (clock-straggle restarts);
#: ``wide`` does not, so its straggle scenario exercises the node pressure
#: plane's Straggling verdict instead.
CHAOS_WORKLOADS = {
    "steady": {"app": {"type": "streams", "width": 2, "pipeline_depth": 1,
                       "source": {"rate_sleep": 0.002}},
               "drain": {"timeout": 15.0, "grace": 0.3},
               "stragglerTimeout": 3.0},
    "wide": {"app": {"type": "streams", "width": 3, "pipeline_depth": 2,
                     "source": {"rate_sleep": 0.002}},
             "drain": {"timeout": 15.0, "grace": 0.3}},
}

#: SLO policies the matrix judges each fault under.
CHAOS_POLICIES = {
    "strict": {"loss_budget": 0, "recovery_time_s": 15.0},
    "relaxed": {"loss_budget": 256, "recovery_time_s": 45.0},
}

#: The scenario matrix: (workload, fault, policy, scenario kwargs).  Every
#: seed is pinned and echoed into the report — a scenario replays exactly
#: (all chaos randomness flows through ``random.Random(seed)``).
#: kill-mid-drain rows run last per workload (they shrink the region).
CHAOS_MATRIX = (
    ("steady", "pod-kill", "strict",
     dict(seed=101, target={"minPe": 1})),
    ("steady", "partition", "strict",
     dict(seed=102, duration=0.6, target={"minPe": 1})),
    ("steady", "clock-straggle", "strict",
     dict(seed=103, duration=1.2, params={"offset": 8.0},
          target={"minPe": 1})),
    ("steady", "node-flap", "relaxed",
     dict(seed=104, duration=0.3)),
    ("steady", "standby-loss", "relaxed",
     dict(seed=106, target={"minPe": 1})),
    ("steady", "kill-mid-drain", "relaxed",
     dict(seed=105, duration=0.05)),
    ("wide", "pod-kill", "relaxed",
     dict(seed=201, target={"minPe": 1})),
    ("wide", "partition", "relaxed",
     dict(seed=202, duration=0.8, target={"minPe": 1})),
    ("wide", "clock-straggle", "relaxed",
     dict(seed=203, duration=1.5, params={"offset": 8.0},
          target={"minPe": 1})),
    ("wide", "kill-mid-drain", "strict",
     dict(seed=204, duration=0.05)),
)


def bench_chaos(out_path: str | None = None) -> dict:
    """The chaos plane end to end: the (workload × fault × policy) scenario
    matrix, every fault injected through the ``FaultInjection`` CRD and
    judged by the SLO verdict plane.

    Per scenario: the span ring is cleared and the job's SLO re-created
    under the scenario's policy (so the verdict judges THIS scenario's
    recover spans, not the run's history), the fault is injected via
    ``run_scenario``, the platform recovers, and a forced SLO evaluation
    folds the evidence into a Met/Violated verdict.  The report carries
    per-scenario seed, terminal phase, recover-span latency, tuples lost
    (drop-ledger delta), and the verdict — ``results/BENCH_chaos.json``.
    """
    scenarios = []
    for workload, spec in CHAOS_WORKLOADS.items():
        p = Platform(num_nodes=4)
        job = f"chaos-{workload}"
        try:
            p.submit(job, spec)
            assert p.wait_full_health(job, 120)
            for wl, fault, policy, kw in CHAOS_MATRIX:
                if wl != workload:
                    continue
                # fresh evidence window: this scenario's spans + a fresh
                # SLO under the scenario's policy (the SLO-delete prune
                # resets the conductor's throttle/spec state too)
                p.trace.clear()
                p.api.slos.delete(crds.slo_name(job))
                p.set_slo(job, **CHAOS_POLICIES[policy])
                dropped_before = p.job_metrics(job).get("tuplesDropped", 0)
                t0 = time.monotonic()
                st = p.run_scenario(fault=fault, job=job, timeout=90, **kw)
                wall_s = time.monotonic() - t0
                assert p.wait_full_health(job, 120), \
                    f"{job}: no full health after {fault}"
                p.slo_conductor.evaluate(job, force=True)
                slo = p.slo_status(job)
                verdicts = {c["type"]: c["status"]
                            for c in slo.get("conditions", ())
                            if c["type"] in ("Met", "Violated")}
                lost = (p.job_metrics(job).get("tuplesDropped", 0)
                        - dropped_before)
                outcome = st.get("outcome") or {}
                row = {
                    "workload": workload, "fault": fault, "policy": policy,
                    "seed": kw["seed"],
                    "completed": st.get("completed", False),
                    "phase": st.get("phase"),
                    "chosen": st.get("chosen"),
                    "recoverS": st.get("recoverS"),
                    "recoverSpanMs": outcome.get("recoverSpanMs"),
                    "wallS": round(wall_s, 4),
                    "tuplesLost": lost,
                    "sloVerdicts": verdicts,
                    "worstRecoveryS": slo.get("ledger", {}).get(
                        "worstRecoveryS"),
                }
                if outcome.get("error"):
                    row["error"] = outcome["error"]
                scenarios.append(row)
                emit(f"chaos.{workload}.{fault}.{policy}",
                     st.get("recoverS") or 0.0,
                     f"{row['phase']};lost={lost};"
                     f"slo={'Met' if verdicts.get('Met') == 'True' else 'Violated'}")
            p.delete_job(job)
            assert p.wait_terminated(job, 60)
        finally:
            p.shutdown()
    report = {
        "benchmark": "chaos",
        "matrix": {"workloads": sorted(CHAOS_WORKLOADS),
                   "policies": CHAOS_POLICIES,
                   "seeds": "per-scenario, recorded (deterministic replay)"},
        "scenarios": scenarios,
        "summary": {
            "total": len(scenarios),
            "recovered": sum(1 for s in scenarios
                             if s["phase"] == "Recovered"),
            "sloMet": sum(1 for s in scenarios
                          if s["sloVerdicts"].get("Met") == "True"),
            "zeroLoss": sum(1 for s in scenarios if s["tuplesLost"] == 0),
        },
    }
    out = out_path or os.path.join(os.path.dirname(__file__), "..", "results",
                                   "BENCH_chaos.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    s = report["summary"]
    emit("chaos.summary", 0.0,
         f"recovered={s['recovered']}/{s['total']};"
         f"sloMet={s['sloMet']};zeroLoss={s['zeroLoss']}")
    return report


def bench_recovery(out_path: str | None = None, n_tuples: int = 600) -> dict:
    """The recovery plane's acceptance bench: cold restart vs warm-standby
    promotion under identical load, the recover *span* (failure detected ->
    replacement connected) as the measured quantity.

    Two runs of the same finite-source streams job.  ``cold``: a pod-kill
    recovers through the full restart chain (launchCount bump -> pod
    conductor recreate -> scheduler decide+bind -> kubelet start -> fabric
    publish -> connected).  ``warm``: a ``StandbyPolicy`` protects the
    victim PE first, so the failover conductor promotes the warm standby in
    place — handle re-key + one epoch bump — and the same ``recover`` span
    closes on the promoted runtime's connect.  Both paths are judged under
    a zero-loss SLO; the report records the speedup (acceptance: >= 5x) and
    end-to-end tuple accounting.  Writes ``results/BENCH_recovery.json``
    (``--smoke`` fails without it).

    Container boot is modeled (``pod_start_delay=0.5``, conservative — real
    image pull + start is seconds): a real kubelet
    pays image pull + process start before a replacement pod's runtime is
    live, and that boot is exactly what warm standby amortizes — the
    standby paid it at creation, off the critical path.  Without the model
    the in-process cold chain costs ~10 ms and the comparison says nothing.
    """
    spec = {"app": {"type": "streams", "width": 2, "pipeline_depth": 1,
                    "source": {"tuples": n_tuples, "rate_sleep": 0.001},
                    "sink": {"report_every": 10}},
            "drain": {"timeout": 15.0, "grace": 0.3}}
    slo_spec = {"loss_budget": 0, "recovery_time_s": 15.0}
    phases = {}
    for mode, seed in (("cold", 301), ("warm", 302)):
        p = Platform(num_nodes=4, pod_start_delay=0.5)
        job = "j"
        try:
            p.submit(job, spec)
            assert p.wait_full_health(job, 120)
            if mode == "warm":
                p.set_standby_policy(job, pes=[1], warm_interval=0.2)
                assert wait_for(
                    lambda: p.api.pes.condition_is(
                        crds.pe_name(job, 1), crds.COND_STANDBY_READY), 30), \
                    "standby never warmed"
            p.set_slo(job, **slo_spec)
            assert wait_for(lambda: _sink_seen(p, job) > 100, 60)
            p.trace.clear()  # this run's recover span only
            st = p.run_scenario(fault="pod-kill", job=job, seed=seed,
                                target={"pe": 1}, timeout=60)
            assert st["completed"], f"{mode}: {st}"
            assert p.wait_full_health(job, 120)
            # quiesce: the finite source completes and the sink count stops
            last = [-1, time.monotonic()]

            def quiesced():
                seen = _sink_seen(p, job)
                if seen != last[0]:
                    last[0] = seen
                    last[1] = time.monotonic()
                return (seen >= n_tuples
                        or time.monotonic() - last[1] > 2.0)

            wait_for(quiesced, 120)
            seen = _sink_seen(p, job)
            p.slo_conductor.evaluate(job, force=True)
            slo = p.slo_status(job)
            verdicts = {c["type"]: c["status"]
                        for c in slo.get("conditions", ())
                        if c["type"] in ("Met", "Violated")}
            recs = [s for s in p.trace.spans(name="recover")
                    if s.attrs.get("job") == job and s.t1 is not None]
            span_s = max(s.t1 - s.t0 for s in recs) if recs else None
            phases[mode] = {
                "seed": seed,
                "recoverSpanS": round(span_s, 6) if span_s else None,
                "recoverSpanMs": (st.get("outcome") or {}).get(
                    "recoverSpanMs"),
                "recoverS": st.get("recoverS"),
                "emitted": n_tuples, "delivered": seen,
                "tuplesLost": n_tuples - seen,
                "metricsDropped": p.job_metrics(job).get("tuplesDropped", 0),
                "sloVerdicts": verdicts,
                "promotions": p.failover.promotions,
                "degradedFailovers": p.failover.degraded_failovers,
                "chain": (p.trace.render(recs[-1]).splitlines()
                          if recs else []),
            }
        finally:
            p.shutdown()
    cold_s = phases["cold"]["recoverSpanS"]
    warm_s = phases["warm"]["recoverSpanS"]
    speedup = (cold_s / warm_s) if cold_s and warm_s else None
    report = {
        "benchmark": "recovery",
        "workload": spec,
        "slo": slo_spec,
        "cold": phases["cold"],
        "warm": phases["warm"],
        "speedup": round(speedup, 2) if speedup else None,
        "acceptance": {"minSpeedup": 5.0,
                       "met": bool(speedup and speedup >= 5.0
                                   and phases["cold"]["tuplesLost"] == 0
                                   and phases["warm"]["tuplesLost"] == 0)},
    }
    out = out_path or os.path.join(os.path.dirname(__file__), "..", "results",
                                   "BENCH_recovery.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    emit("recovery.cold_span", cold_s or 0.0,
         f"lost={phases['cold']['tuplesLost']};"
         f"slo={'Met' if phases['cold']['sloVerdicts'].get('Met') == 'True' else 'Violated'}")
    emit("recovery.warm_span", warm_s or 0.0,
         f"lost={phases['warm']['tuplesLost']};"
         f"slo={'Met' if phases['warm']['sloVerdicts'].get('Met') == 'True' else 'Violated'}")
    emit("recovery.speedup", 0.0,
         f"{report['speedup']}x;acceptance="
         f"{'met' if report['acceptance']['met'] else 'MISSED'}")
    return report


# ------------------------------------------------------------------ serve


def _serve_trace(kind: str, n: int, prefix_len: int = 16,
                 unique_len: int = 4, max_new: int = 8) -> list:
    """Request mix for the serve bench: ``shared`` prompts agree on a
    ``prefix_len``-token prefix then diverge; ``disjoint`` prompts share
    nothing.  Same total prompt tokens either way."""
    prompts = []
    for i in range(n):
        if kind == "shared":
            prompts.append([7] * prefix_len + [11 + i] * unique_len)
        else:
            prompts.append([11 + i] * (prefix_len + unique_len))
    return [(i, p, max_new) for i, p in enumerate(prompts)]


def _drive_serve_engine(eng, trace, make_request) -> dict:
    """Submit ``trace`` and step the engine to drain, timing tokens/sec,
    per-request TTFT percentiles, and peak admitted concurrency."""
    for rid, prompt, max_new in trace:
        eng.submit(make_request(rid, prompt, max_new))
    first: dict = {}
    peak = 0
    t0 = time.monotonic()
    ticks = 0
    while (eng.queue or eng.slots_busy) and ticks < 5000:
        out = eng.step()
        now = time.monotonic()
        peak = max(peak, eng.slots_busy)
        for rid, _tok in out:
            first.setdefault(rid, now - t0)
        ticks += 1
    wall = time.monotonic() - t0
    gen = sum(len(r.generated) for r in eng.finished)
    ttfts = sorted(first.values())

    def pct(q: float) -> float:
        return ttfts[min(len(ttfts) - 1, int(q * len(ttfts)))] if ttfts else 0.0

    return {"wall_s": round(wall, 4),
            "tokensPerSec": round(gen / wall, 2) if wall else 0.0,
            "generated": gen, "finished": len(eng.finished),
            "ttft_p50_s": round(pct(0.50), 4),
            "ttft_p99_s": round(pct(0.99), 4),
            "peakConcurrency": peak}


def bench_serve(out_path: str | None = None, n_requests: int = 12) -> dict:
    """Paged KV-cache serving vs the fixed-slot baseline at an equal HBM
    budget (paper §serving; the PR's tentpole acceptance gate).

    Both engines run the same reduced model and the same request mixes —
    ``shared`` (common 16-token prompt prefix, then divergence) and
    ``disjoint`` (no sharing) — under the same 256-token KV budget:

    - fixed: ``ServeEngine``, 4 slots x 64-token padded caches (admission
      capacity is the slot count, regardless of request length);
    - paged: ``PagedServeEngine``, 32 usable 8-token blocks + banker's
      admission (capacity scales with actual footprints), chunked prefill,
      prefix cache + copy-on-write.

    Reports tokens/sec, TTFT p50/p99, peak admitted concurrency, and the
    paged engine's pool/prefix signals per mix.  Acceptance: paged beats
    fixed on tokens/sec AND p99 TTFT on both mixes, admits >= 2x the
    concurrent requests at the same budget, and shows a nonzero prefix hit
    rate on the shared mix.  Writes ``results/BENCH_serve.json``
    (``--smoke`` fails without it)."""
    import jax as _jax

    from repro.configs import reduced_config
    from repro.models import ModelOptions, init_params
    from repro.serve import PagedServeEngine, Request, ServeEngine

    cfg = reduced_config("gemma-2b")
    opts = ModelOptions(compute_dtype="float32")
    params = init_params(_jax.random.key(0), cfg)
    budget_tokens = 256  # 4 slots x 64 == 32 usable blocks x 8

    def make_fixed():
        return ServeEngine(cfg, params, num_slots=4, max_len=64, opts=opts)

    def make_paged():
        return PagedServeEngine(cfg, params, num_blocks=33, block_size=8,
                                max_active=16, prefill_chunk=8, opts=opts)

    def warmup(eng):  # compile every (admit/prefill/decode) shape off-clock
        eng.submit(Request(rid=-1, prompt=[3] * 20, max_new_tokens=2))
        eng.run_until_drained(max_ticks=200)
        eng.finished.clear()

    mixes: dict = {}
    for mix in ("shared", "disjoint"):
        trace = _serve_trace(mix, n_requests)
        row: dict = {}
        for name, make in (("fixed", make_fixed), ("paged", make_paged)):
            eng = make()
            warmup(eng)
            row[name] = _drive_serve_engine(
                eng, trace, lambda rid, p, m: Request(rid=rid, prompt=p,
                                                      max_new_tokens=m))
            if name == "paged":
                m = eng.metrics()
                row[name]["engine"] = {
                    k: m[k] for k in ("blocksTotal", "blocksFree",
                                      "blocksCached", "prefixHitRate",
                                      "prefillBacklog", "cowCopies")}
        row["speedup"] = round(row["paged"]["tokensPerSec"]
                               / max(row["fixed"]["tokensPerSec"], 1e-9), 2)
        row["ttftGain"] = round(row["fixed"]["ttft_p99_s"]
                                / max(row["paged"]["ttft_p99_s"], 1e-9), 2)
        row["capacityGain"] = round(row["paged"]["peakConcurrency"]
                                    / max(row["fixed"]["peakConcurrency"], 1),
                                    2)
        mixes[mix] = row
    accept = {
        "pagedFasterTokens": all(m["speedup"] > 1.0 for m in mixes.values()),
        "pagedFasterTtftP99": all(m["ttftGain"] > 1.0 for m in mixes.values()),
        "capacityGain2x": all(m["capacityGain"] >= 2.0
                              for m in mixes.values()),
        "prefixHitsOnSharedMix":
            mixes["shared"]["paged"]["engine"]["prefixHitRate"] > 0.0,
    }
    report = {
        "benchmark": "serve",
        "model": "gemma-2b (reduced)",
        "budgetTokens": budget_tokens,
        "requests": n_requests,
        "fixed": {"numSlots": 4, "maxLen": 64},
        "paged": {"blocks": 32, "blockSize": 8, "maxActive": 16,
                  "prefillChunk": 8},
        "mixes": mixes,
        "acceptance": {**accept, "met": all(accept.values())},
    }
    out = out_path or os.path.join(os.path.dirname(__file__), "..", "results",
                                   "BENCH_serve.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    for mix, row in mixes.items():
        emit(f"serve.{mix}.tokens_per_sec", 0.0,
             f"fixed={row['fixed']['tokensPerSec']};"
             f"paged={row['paged']['tokensPerSec']};x{row['speedup']}")
        emit(f"serve.{mix}.ttft_p99_s", 0.0,
             f"fixed={row['fixed']['ttft_p99_s']};"
             f"paged={row['paged']['ttft_p99_s']};x{row['ttftGain']}")
        emit(f"serve.{mix}.capacity", 0.0,
             f"fixed={row['fixed']['peakConcurrency']};"
             f"paged={row['paged']['peakConcurrency']};"
             f"x{row['capacityGain']}")
    emit("serve.prefix_hit_rate", 0.0,
         str(mixes["shared"]["paged"]["engine"]["prefixHitRate"]))
    emit("serve.acceptance", 0.0,
         "met" if report["acceptance"]["met"] else "MISSED")
    return report


BENCHES = {
    "fig7": bench_fig7_job_lifecycle,
    "fig7c": bench_fig7c_gc_vs_bulk,
    "fig8": bench_fig8_pe_throughput,
    "fig9": bench_fig9_width_change,
    "fig10": bench_fig10_pe_failure_recovery,
    "fig11": bench_fig11_cr_recovery,
    "table1": bench_table1_loc,
    "roofline": bench_roofline,
    "autoscale": bench_autoscale_rampup,
    "transport": bench_transport,
    "scale_down": bench_scaledown,
    "scaleout": bench_scaleout,
    "teardown": bench_teardown,
    "oversub": bench_oversub,
    "latency": bench_latency,
    "chaos": bench_chaos,
    "recovery": bench_recovery,
    "serve": bench_serve,
}

# cheap subset for CI (`--smoke`): seconds not minutes (scale_down and
# oversub are the Platform spin-ups — a few seconds per mode — because
# zero-loss scale-down and pressure-aware scheduling are acceptance
# criteria, not just trajectories)
SMOKE = ("fig7c", "table1", "transport", "scale_down", "scaleout", "teardown",
         "oversub", "latency", "chaos", "recovery", "serve")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = sys.argv[1:]
    smoke = "--smoke" in args
    only = list(SMOKE) if smoke else (args or list(BENCHES))
    errors = 0
    print("name,us_per_call,derived")
    for name in only:
        try:
            BENCHES[name]()
        except Exception as exc:  # noqa: BLE001 — isolate benchmark failures
            import traceback

            traceback.print_exc()
            emit(f"{name}.ERROR", 0.0, repr(exc))
            errors += 1
    out = os.path.join(os.path.dirname(__file__), "..", "results",
                       "benchmarks.csv")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write("name,us_per_call,derived\n")
        for name, us, derived in ROWS:
            f.write(f"{name},{us:.1f},{derived}\n")
    if smoke:  # the CI guard must actually guard
        results_dir = os.path.join(os.path.dirname(__file__), "..", "results")
        for artifact in ("BENCH_transport.json", "BENCH_scaledown.json",
                         "BENCH_scaleout.json", "BENCH_latency.json",
                         "BENCH_chaos.json", "BENCH_teardown.json",
                         "BENCH_oversub.json", "BENCH_recovery.json",
                         "BENCH_serve.json"):
            if not os.path.exists(os.path.join(results_dir, artifact)):
                print(f"SMOKE FAIL: results/{artifact} not produced",
                      flush=True)
                errors += 1
        if errors:
            sys.exit(1)


if __name__ == "__main__":
    main()
