"""Unit tests: HLO analyzer (trip counts, dot FLOPs, collectives), roofline
terms, gradient compression math, fabric collectives, straggler monitor,
config invariants."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze, parse_module
from repro.launch.roofline import roofline_terms

SYNTH_HLO = """
HloModule jit_step, is_scheduled=true

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,128]{1,0} get-tuple-element(%p), index=1
  %w = f32[128,128]{1,0} constant(0)
  %y = f32[8,128]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,128]{1,0} all-reduce(%y), channel_id=1, replica_groups=[16,16]<=[256]
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,128]{1,0}) tuple(%i2, %ar)
}

%cond (p: (s32[], f32[8,128])) -> pred[] {
  %p = (s32[], f32[8,128]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,128]) -> f32[8,128] {
  %a = f32[8,128]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,128]{1,0}) tuple(%z, %a)
  %w = (s32[], f32[8,128]{1,0}) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8,128]{1,0} get-tuple-element(%w), index=1
}
"""


def test_hlo_parse_and_trip_counts():
    comps, entry = parse_module(SYNTH_HLO)
    assert entry == "main"
    assert set(comps) == {"body", "cond", "main"}
    t = analyze(SYNTH_HLO)
    # dot: 2*8*128*128 flops, x10 loop trips
    assert t.flops == pytest.approx(2 * 8 * 128 * 128 * 10)
    # all-reduce ring wire: 2 * N * (g-1)/g, x10
    n = 8 * 128 * 4
    assert t.coll_bytes == pytest.approx(2 * n * 15 / 16 * 10)
    assert "all-reduce/g16" in t.coll_by_key
    assert t.unknown_trip_loops == 0


def test_roofline_terms_and_dominant():
    terms = roofline_terms(197e12, 0.0, 0.0, 256)  # 1s of pure compute
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["dominant"] == "compute"
    assert terms["roofline_fraction_compute"] == pytest.approx(1.0)
    terms = roofline_terms(197e10, 819e9 * 4, 0.0, 256)  # memory-bound
    assert terms["dominant"] == "memory"
    assert terms["roofline_fraction_compute"] == pytest.approx(0.01 / 2.0)


def test_quantize_roundtrip_and_error_feedback():
    from repro.train.compress import ef_quantize_mean, quantize_int8

    g = jnp.asarray([[1.0, -2.0, 0.5, 127.0]])
    q, scale = quantize_int8(g)
    deq = q.astype(jnp.float32) * scale
    assert float(jnp.max(jnp.abs(deq - g))) <= float(scale) / 2 + 1e-6
    # EF: errors accumulate and are re-applied
    grads_g = {"w": jnp.stack([g, g * 0.3])}  # 2 pods
    ef0 = {"w": jnp.zeros_like(grads_g["w"])}
    mean1, ef1 = ef_quantize_mean(grads_g, ef0)
    assert mean1["w"].shape == g.shape
    # applying the same grads with the EF buffer shifts the next quantization
    mean2, ef2 = ef_quantize_mean(grads_g, ef1)
    two_step = (np.asarray(mean1["w"]) + np.asarray(mean2["w"]))
    exact = np.asarray(jnp.mean(grads_g["w"], 0)) * 2
    assert np.max(np.abs(two_step - exact)) < np.max(np.abs(exact)) * 0.05


def test_collective_group_epoch_abort():
    import threading

    from repro.platform.fabric import CollectiveGroup, EpochAborted

    grp = CollectiveGroup(width=2)
    results = {}

    def contribute(rank):
        try:
            results[rank] = grp.allreduce_mean("k", [np.ones(3) * (rank + 1)],
                                               epoch=0, timeout=5, rank=rank)
        except EpochAborted as e:
            results[rank] = ("aborted", e.epoch)

    t = threading.Thread(target=contribute, args=(0,))
    t.start()
    time.sleep(0.1)
    grp.abort()  # rank 0 is stuck at the barrier -> must abort, not hang
    t.join(timeout=5)
    assert results[0] == ("aborted", 1)
    # new epoch works
    t1 = threading.Thread(target=contribute, args=(0,))
    results.clear()

    def c2():
        results[1] = grp.allreduce_mean("k", [np.ones(3) * 2], epoch=1,
                                        timeout=5, rank=1)

    def c1():
        results[0] = grp.allreduce_mean("k", [np.ones(3) * 1], epoch=1,
                                        timeout=5, rank=0)

    a, b = threading.Thread(target=c1), threading.Thread(target=c2)
    a.start(); b.start(); a.join(5); b.join(5)
    np.testing.assert_allclose(results[0][0], np.ones(3) * 1.5)


def test_straggler_monitor_marks_stale_pods():
    from repro.core import wait_for
    from repro.platform import Platform, crds

    p = Platform(num_nodes=0, with_cluster=False)
    try:
        p.store.create(crds.make_job("j", {"app": {"type": "streams"},
                                           "stragglerTimeout": 5.0}))
        pod = crds.make_pod("j", 0, {}, launch_count=1, generation=1)
        p.store.create(pod)
        stale_uid = p.store.get(crds.POD, pod.name).uid
        p.store.update_status(crds.POD, pod.name,
                              {"phase": "Running", "heartbeat": time.time() - 60})
        fresh = crds.make_pod("j", 1, {}, launch_count=1, generation=1)
        p.store.create(fresh)
        p.store.update_status(crds.POD, fresh.name,
                              {"phase": "Running", "heartbeat": time.time()})
        marked = p.straggler_monitor.scan()
        assert marked == [pod.name]
        # the normal failure causal chain takes over: pod controller deletes
        # the failed pod (kind may already be deleted by the controller).
        # The job's own pod conductor may reuse the name for a new pod, so
        # the stale incarnation is tracked by uid.
        def stale_gone():
            cur = p.store.try_get(crds.POD, pod.name)
            return cur is None or cur.uid != stale_uid

        assert wait_for(stale_gone, 10)
        assert p.store.exists(crds.POD, fresh.name)
    finally:
        p.shutdown()


def test_config_invariants():
    from repro.configs import ARCH_IDS, get_config

    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.padded_vocab % 128 == 0
        assert cfg.padded_vocab >= cfg.vocab_size
        assert cfg.num_layers == len(cfg.layer_kinds)
        assert cfg.active_param_count() <= cfg.param_count()
        if cfg.moe:
            assert cfg.active_param_count() < cfg.param_count()


def test_slstm_custom_vjp_matches_autodiff():
    import repro.models.recurrent as rec

    B, S, d, H = 2, 16, 16, 2
    ks = jax.random.split(jax.random.key(3), 2)
    params = rec.init_slstm(ks[0], d, H)
    x = jax.random.normal(ks[1], (B, S, d), jnp.float32) * 0.5

    def run(custom):
        old = rec.SLSTM_CUSTOM_VJP
        rec.SLSTM_CUSTOM_VJP = custom
        try:
            def f(p):
                out = rec.slstm_seq(p, x, H)
                return jnp.sum(out * jnp.sin(jnp.arange(out.size).reshape(out.shape)))
            val, grads = jax.value_and_grad(f)(params)
        finally:
            rec.SLSTM_CUSTOM_VJP = old
        return val, grads

    v1, g1 = run(False)
    v2, g2 = run(True)
    assert abs(float(v1 - v2)) < 1e-4
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
