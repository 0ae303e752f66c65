"""Runtime: partition executor equivalence, serving, scheduler, fault."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve
from repro.models import build
from repro.models.transformer import lm_hidden, lm_logits
from repro.runtime.partition import (LMSplitExecutor, SplitPlan,
                                     VLASplitExecutor, chunk_payload,
                                     merge_chunks, payload_bytes)
from repro.runtime.scheduler import (ElasticPool, MicroBatcher, Request,
                                     StragglerMitigator)
from repro.runtime.serving import greedy_generate


@pytest.fixture(scope="module")
def lm_setup():
    cfg = get_config("llama3.2-3b").reduced().replace(n_layers=6,
                                                      dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    h, _ = lm_hidden(cfg, params, tokens, remat=False)
    ref = lm_logits(cfg, params, h)
    return cfg, model, params, tokens, ref


def test_lm_split_equivalence_all_pool_positions(lm_setup):
    cfg, model, params, tokens, ref = lm_setup
    ex = LMSplitExecutor(cfg, SplitPlan(2, 5))
    for split in range(2, 6):
        logits, payload = ex.run(params, tokens, split)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_lm_split_codec_halves_payload(lm_setup):
    cfg, model, params, tokens, ref = lm_setup
    raw = LMSplitExecutor(cfg, SplitPlan(2, 5))
    qz = LMSplitExecutor(cfg, SplitPlan(2, 5, codec="int8"))
    _, p_raw = raw.run(params, tokens, 3)
    logits, p_q = qz.run(params, tokens, 3)
    assert payload_bytes(p_q) < 0.6 * payload_bytes(p_raw)
    rel = float(jnp.max(jnp.abs(logits - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 0.05     # int8 cut tensor stays within a few percent


def test_lm_two_pool_equivalence_all_cut_pairs(lm_setup):
    """Two-pool (edge→cloud→edge) forward must match the monolithic
    forward for EVERY (split, split2) inside the pools — and moving either
    cut must not retrigger compilation (the cuts are traced arguments)."""
    cfg, model, params, tokens, ref = lm_setup
    traces = {"edge": 0, "mid": 0, "tail": 0}
    ex = LMSplitExecutor(cfg, SplitPlan(1, 3, pool2_start=4, pool2_end=6))

    orig_edge, orig_mid, orig_tail = (ex._edge_fwd, ex._cloud_mid_fwd,
                                      ex._tail_fwd)

    def count(name, fn):
        def wrapped(*a):
            traces[name] += 1
            return fn(*a)
        return wrapped

    ex._edge = jax.jit(count("edge", orig_edge))
    ex._cloud_mid = jax.jit(count("mid", orig_mid))
    ex._tail = jax.jit(count("tail", orig_tail))
    for split in range(1, 4):
        for split2 in range(4, 7):
            logits, payloads = ex.run(params, tokens, split,
                                      split2=split2)
            assert set(payloads) == {"up", "down"}
            np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
    # one trace per function across all 9 (split, split2) pairs
    assert traces == {"edge": 1, "mid": 1, "tail": 1}


def test_vla_two_pool_equivalence():
    cfg = get_config("cogact-7b").reduced().replace(n_layers=6)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(3)
    patches = jax.random.normal(key, (2, cfg.n_patches, cfg.vit_dim))
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    ref = model.forward(params, {"patches": patches, "tokens": tokens}, key)
    Lv = cfg.vit_layers
    ex = VLASplitExecutor(cfg, SplitPlan(Lv + 1, Lv + 2,
                                         pool2_start=Lv + 4,
                                         pool2_end=Lv + 6))
    for split in (Lv + 1, Lv + 2):
        for split2 in (Lv + 4, Lv + 5, Lv + 6):
            act, payloads = ex.run(params, patches, tokens, split, key,
                                   split2=split2)
            assert set(payloads) == {"up", "down"}
            np.testing.assert_allclose(np.asarray(act), np.asarray(ref),
                                       atol=1e-5)


def test_vla_two_pool_codec_payloads():
    """Downlink codec ships a real compressed payload and the edge-tail
    action stays close to the monolithic reference."""
    cfg = get_config("cogact-7b").reduced().replace(n_layers=6)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(3)
    patches = jax.random.normal(key, (2, cfg.n_patches, cfg.vit_dim))
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    ref = model.forward(params, {"patches": patches, "tokens": tokens}, key)
    Lv = cfg.vit_layers
    raw = VLASplitExecutor(cfg, SplitPlan(Lv + 1, Lv + 2,
                                          pool2_start=Lv + 4,
                                          pool2_end=Lv + 6))
    qz = VLASplitExecutor(cfg, SplitPlan(Lv + 1, Lv + 2, codec="int8",
                                         pool2_start=Lv + 4,
                                         pool2_end=Lv + 6, codec2="int8"))
    _, p_raw = raw.run(params, patches, tokens, Lv + 2, key, split2=Lv + 5)
    act, p_q = qz.run(params, patches, tokens, Lv + 2, key, split2=Lv + 5)
    assert payload_bytes(p_q["up"]) < 0.6 * payload_bytes(p_raw["up"])
    assert payload_bytes(p_q["down"]) < 0.6 * payload_bytes(p_raw["down"])
    np.testing.assert_allclose(np.asarray(act), np.asarray(ref), atol=0.2)


def test_vla_two_pool_semantic_downlink_slice():
    """A degenerate pool 2 at the graph end makes the tail exactly the
    action stage: the downlink ships only the semantic conditioning slice
    (the bytes the planner prices via in_transfer_bytes), not the full
    sequence — and the action still matches the monolithic forward."""
    cfg = get_config("cogact-7b").reduced().replace(n_layers=6)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(3)
    patches = jax.random.normal(key, (2, cfg.n_patches, cfg.vit_dim))
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    ref = model.forward(params, {"patches": patches, "tokens": tokens}, key)
    Lv, L = cfg.vit_layers, cfg.vit_layers + cfg.n_layers
    ex = VLASplitExecutor(cfg, SplitPlan(Lv + 1, Lv + 3,
                                         pool2_start=L, pool2_end=L))
    act, payloads = ex.run(params, patches, tokens, Lv + 2, key)
    np.testing.assert_allclose(np.asarray(act), np.asarray(ref), atol=1e-5)
    # DiT head reads the single cognition token: 1 × d_model on the wire
    seq = cfg.n_patches + tokens.shape[1]
    assert payloads["down"]["x"].shape[1] == 1
    assert payload_bytes(payloads["down"]) < payload_bytes(payloads["up"]) / seq * 2


def test_chunk_payload_partitions_bytes_and_merges_exactly():
    """Chunk slices partition the payload bytes exactly and reassemble
    bit-identically — for raw, int8 and int4 wire formats."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 13, 256))
    from repro.runtime.partition import decode_activation, encode_activation
    for codec in ("", "int8", "int4"):
        payload = encode_activation(x.astype(jnp.bfloat16), codec)
        for k in (1, 2, 4, 13, 20):          # incl. empty chunks (k > S)
            chunks = chunk_payload(payload, k)
            assert len(chunks) == k
            assert sum(payload_bytes(c) for c in chunks) == \
                payload_bytes(payload)
            merged = merge_chunks(chunks)
            ref = decode_activation(payload)
            got = decode_activation(merged)
            assert np.array_equal(np.asarray(ref), np.asarray(got)), \
                (codec, k)


def test_lm_run_streamed_bit_identical_no_retrace(lm_setup):
    """Streamed transport must change NOTHING numerically — chunked
    shipping reassembles to the exact payload — and the jitted forwards
    must not retrace when the chunk count changes between requests."""
    cfg, model, params, tokens, ref = lm_setup
    traces = {"edge": 0, "cloud": 0}
    ex = LMSplitExecutor(cfg, SplitPlan(2, 5, codec="int8"))
    orig_edge, orig_cloud = ex._edge_fwd, ex._cloud_fwd

    def count(name, fn):
        def wrapped(*a):
            traces[name] += 1
            return fn(*a)
        return wrapped

    ex._edge = jax.jit(count("edge", orig_edge))
    ex._cloud = jax.jit(count("cloud", orig_cloud))
    base, payload = ex.run(params, tokens, 3)
    for k in (1, 2, 3, 5, 12):
        logits, chunks = ex.run_streamed(params, tokens, 3, k)
        assert len(chunks) == k
        assert np.array_equal(np.asarray(logits), np.asarray(base)), k
        assert sum(payload_bytes(c) for c in chunks) == \
            payload_bytes(payload)
    # one trace per function across the monolithic run AND all chunk
    # counts — the chunk count never reaches a traced function
    assert traces == {"edge": 1, "cloud": 1}


def test_lm_two_pool_run_streamed_bit_identical(lm_setup):
    cfg, model, params, tokens, ref = lm_setup
    ex = LMSplitExecutor(cfg, SplitPlan(1, 3, pool2_start=4, pool2_end=6))
    base, _ = ex.run(params, tokens, 2, split2=5)
    logits, payloads = ex.run_streamed(params, tokens, 2, 4, split2=5)
    assert np.array_equal(np.asarray(logits), np.asarray(base))
    assert isinstance(payloads["up"], list) and len(payloads["up"]) == 4
    assert isinstance(payloads["down"], dict)  # small tail never streams


def test_vla_run_streamed_bit_identical():
    cfg = get_config("cogact-7b").reduced().replace(n_layers=6)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(3)
    patches = jax.random.normal(key, (2, cfg.n_patches, cfg.vit_dim))
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    Lv = cfg.vit_layers
    ex = VLASplitExecutor(cfg, SplitPlan(Lv + 1, Lv + 3, codec="int8"))
    base, _ = ex.run(params, patches, tokens, Lv + 2, key)
    for k in (1, 3, 8):
        act, chunks = ex.run_streamed(params, patches, tokens, Lv + 2, k,
                                      key=key)
        assert np.array_equal(np.asarray(act), np.asarray(base)), k
        assert len(chunks) == k


def test_moe_split_equivalence():
    cfg = get_config("granite-moe-3b-a800m").reduced().replace(
        n_layers=4, dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                                cfg.vocab_size)
    h, _ = lm_hidden(cfg, params, tokens, remat=False)
    ref = lm_logits(cfg, params, h)
    ex = LMSplitExecutor(cfg, SplitPlan(1, 3))
    logits, _ = ex.run(params, tokens, 2)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_vla_split_equivalence():
    cfg = get_config("cogact-7b").reduced().replace(n_layers=4)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(3)
    patches = jax.random.normal(key, (2, cfg.n_patches, cfg.vit_dim))
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    ref = model.forward(params, {"patches": patches, "tokens": tokens}, key)
    Lv = cfg.vit_layers
    ex = VLASplitExecutor(cfg, SplitPlan(Lv + 1, Lv + 3))
    act, _ = ex.run(params, patches, tokens, Lv + 2, key)
    np.testing.assert_allclose(np.asarray(act), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("arch", ["llama3.2-3b", "openvla-7b", "cogact-7b",
                                  "kimi-vl-a3b-vla"])
def test_moving_the_split_traces_each_tier_once(arch):
    """For a dense LM, a VLA with the detok head, one with the DiT head
    and the MoE VLA (at ``test_moe_vla``'s CPU size): moving the split
    across its whole pool traces each tier once, and the tiers' programs
    keep the names ``_edge_fwd`` / ``_cloud_fwd`` that the benchmark's
    device-trace readers match."""
    from test_moe_vla import SMALL
    cfg = get_config(arch)
    cfg = cfg.replace(**SMALL) if cfg.moe_dropless else \
        cfg.reduced().replace(n_layers=3)
    lo, hi = cfg.vit_layers, cfg.vit_layers + cfg.n_layers
    cls = VLASplitExecutor if cfg.family == "vla" else LMSplitExecutor
    ex = cls(cfg, SplitPlan(lo, hi, codec="int8"))
    params = build(cfg).init(jax.random.PRNGKey(0))
    inputs = serve.make_inputs(cfg, jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    traces = {"_front": 0, "_head": 0}

    def count(name, fn):
        def wrapped(*a):
            traces[name] += 1
            return fn(*a)
        return wrapped

    # the hooks run only while their tier's program is traced
    ex._front = count("_front", ex._front)
    ex._head = count("_head", ex._head)
    for split in range(lo, hi + 1):
        out, payload = ex.run(params, *inputs, split, key)
        assert np.isfinite(np.asarray(out, np.float32)).all()
    assert traces == {"_front": 1, "_head": 1}
    edge_args = (params, *inputs, jnp.int32(lo))
    cloud_args = (params, jax.eval_shape(ex._edge, *edge_args),
                  jnp.int32(lo), key)
    edge = ex._edge.lower(*edge_args)
    assert edge.as_text().startswith("module @jit__edge_fwd ")
    assert ex._cloud.lower(*cloud_args).as_text().startswith(
        "module @jit__cloud_fwd ")
    # the edge program's parameters carry the family's input names, as
    # the per-family programs' did
    entry = edge.compile().as_text().split("\nENTRY ")[1]
    for name in (*ex.INPUTS, "split"):
        assert f"%{name}." in entry, name


def test_greedy_generate():
    cfg = get_config("llama3.2-3b").reduced().replace(n_layers=2)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                cfg.vocab_size)
    out = greedy_generate(model, params, {"tokens": tokens}, n_steps=5)
    assert out.shape == (2, 5)
    assert bool(jnp.all((out >= 0) & (out < cfg.vocab_size)))


# ------------------------------------------------------------- scheduler
def test_microbatcher_forms_on_size_and_timeout():
    mb = MicroBatcher(batch_size=3, max_wait_s=0.5)
    mb.add(Request(0, 0.0, 4))
    assert mb.maybe_form(0.1) is None
    mb.add(Request(1, 0.1, 4))
    mb.add(Request(2, 0.1, 4))
    b = mb.maybe_form(0.2)
    assert b is not None and len(b.requests) == 3
    mb.add(Request(3, 1.0, 4))
    assert mb.maybe_form(1.1) is None
    b2 = mb.maybe_form(1.6)          # timeout fires
    assert b2 is not None and len(b2.requests) == 1


def test_straggler_hedging_prefers_fast_replica():
    sm = StragglerMitigator()
    lat = {"fast": 0.01, "slow": 0.10}
    seq = {"n": 0}

    def exec_fn(r):
        seq["n"] += 1
        # one tail event on 'fast' after warmup
        if r == "fast" and seq["n"] == 30:
            return 1.0
        return lat[r]

    outs = [sm.run(["fast", "slow"], exec_fn) for _ in range(40)]
    assert sum(o.hedged for o in outs) >= 1
    hedged = [o for o in outs if o.hedged]
    assert all(o.latency_s < 1.0 for o in hedged)  # hedge rescued the tail
    # before the tail event, routing should prefer the fast replica
    assert all(o.replica == "fast" for o in outs[5:29])


def test_elastic_pool_detects_loss():
    events = []
    pool = ElasticPool(on_change=lambda live: events.append(tuple(live)),
                       timeout_s=1.0)
    pool.heartbeat("edge", 0.0)
    pool.heartbeat("cloud", 0.0)
    assert pool.live(0.5) == ["cloud", "edge"]
    pool.heartbeat("cloud", 2.0)     # edge went silent
    assert pool.live(2.0) == ["cloud"]
    assert events[-1] == ("cloud",)
