"""Split-boundary transport codecs: kernel roundtrips (int8 + packed int4,
jnp vs Pallas-interpret vs ref), cost-model invariants, codec-aware planner
parity (scalar search_joint vs vectorized codec axis on every registered
config), and the joint split×codec ΔNB adjustment."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.core import (CODECS, Thresholds, Workload, adjust, build_graph,
                        build_pool, evaluate_split, get_codec, search,
                        search_joint, search_vec, sweep_search, transport_s)
from repro.core.codec import (DeltaCodec, make_codecs, make_delta_codec,
                              resolve_codecs)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False
from repro.core.hardware import A100, ORIN
from repro.core.segmentation import cut_bytes, graph_arrays
from repro.kernels.activation_codec import ops as codec_ops, ref as codec_ref

BWS = np.geomspace(0.05e6, 100e6, 13)
W = Workload()
AXIS = ("identity", "int8", "int4", "topk", "fp16")


# -------------------------------------------------------- int4 kernel layer
@pytest.mark.parametrize("shape", [(4, 256), (8, 512), (2, 3, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int4_roundtrip_error_bound(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    p, s = codec_ops.quantize_int4(x)
    back = codec_ops.dequantize_int4(p, s, dtype)
    xf = np.asarray(x, np.float32).reshape(-1, 128)
    bf = np.asarray(back, np.float32).reshape(-1, 128)
    amax = np.abs(xf).max(-1, keepdims=True)
    assert np.all(np.abs(bf - xf) <= amax / 7.0 * 1.01 + 1e-6)


@pytest.mark.parametrize("shape", [(4, 256), (256, 512)])
def test_int4_impls_agree_exactly(shape):
    """jnp oracle, Pallas-interpret kernel and eager ref must agree
    bit-for-bit (packing layout AND scales)."""
    x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.bfloat16)
    pr, sr = codec_ref.quantize_int4(x)
    pj, sj = codec_ops.quantize_int4(x, impl="jnp")
    pi, si = codec_ops.quantize_int4(x, impl="interpret")
    assert bool(jnp.all(pr == pj)) and bool(jnp.all(pr == pi))
    assert bool(jnp.all(sr == sj)) and bool(jnp.all(sr == si))
    br = codec_ref.dequantize_int4(pr, sr)
    bj = codec_ops.dequantize_int4(pj, sj, impl="jnp")
    bi = codec_ops.dequantize_int4(pi, si, impl="interpret")
    assert bool(jnp.all(br == bj)) and bool(jnp.all(br == bi))


def test_int8_impls_agree_exactly():
    x = jax.random.normal(jax.random.PRNGKey(2), (256, 512), jnp.bfloat16)
    qr, sr = codec_ref.quantize_int8(x)
    qi, si = codec_ops.quantize(x, impl="interpret")
    assert bool(jnp.all(qr == qi))
    np.testing.assert_allclose(np.asarray(sr), np.asarray(si))


def test_int4_packing_layout():
    """Byte j of each 256-lane pair holds elements j (low nibble) and
    j + 128 (high nibble), biased by +7 with a -128 byte offset."""
    x = jnp.arange(-128, 128, dtype=jnp.float32).reshape(1, 256) / 18.3
    p, s = codec_ref.quantize_int4(x)
    pv = np.asarray(p, np.int64) + 128
    lo, hi = pv % 16 - 7, pv // 16 - 7
    xf = np.asarray(x, np.float32)
    sf = np.asarray(s, np.float32)
    np.testing.assert_allclose(
        lo[0], np.clip(np.round(xf[0, :128] / sf[0, 0]), -7, 7))
    np.testing.assert_allclose(
        hi[0], np.clip(np.round(xf[0, 128:] / sf[0, 1]), -7, 7))


def test_wire_bytes_match_codec_model():
    """The analytic Codec wire model must equal the real packed payload."""
    shape = (1, 17, 3072)
    n = 17 * 3072
    int8, int4 = CODECS["int8"], CODECS["int4"]
    assert codec_ref.wire_bytes(shape) == int8.wire_bytes(n * 2)
    assert codec_ref.wire_bytes_int4(shape) == int4.wire_bytes(n * 2)


# ----------------------------------------------------------- codec pricing
def test_identity_codec_is_free():
    ident = CODECS["identity"]
    assert ident.wire_factor == 1.0
    assert ident.encode_s(1e6, ORIN) == 0.0
    assert ident.decode_s(1e6, A100) == 0.0
    assert ident.err_bound == 0.0


def test_codec_costs_linear_and_ordered():
    int8, int4 = CODECS["int8"], CODECS["int4"]
    assert int4.wire_factor < int8.wire_factor < 1.0
    assert int4.err_bound > int8.err_bound > 0.0
    # linearity is what lets the vectorized planner fold codecs in
    assert int8.encode_s(2e6, ORIN) == pytest.approx(
        2 * int8.encode_s(1e6, ORIN))
    # transport includes both sides' compute when devices are given
    raw = 1e6
    t_wire = int8.wire_bytes(raw) / 1e6
    assert transport_s(raw, 1e6, int8, ORIN, A100) > t_wire
    assert transport_s(raw, 1e6, int8) == pytest.approx(t_wire)


def test_resolve_codecs_max_err_gate():
    cs = resolve_codecs(("identity", "int8", "int4"), max_err=0.01)
    assert [c.name for c in cs] == ["identity", "int8"]
    with pytest.raises(ValueError):
        resolve_codecs(("int4",), max_err=1e-6)
    assert resolve_codecs(None) is None


def test_make_codecs_f32_raw():
    cs = make_codecs(raw_bytes_per_elem=4.0)
    assert cs["fp16"].wire_factor == pytest.approx(0.5)
    assert cs["int8"].wire_factor == pytest.approx((1 + 4 / 128) / 4)


# ------------------------------------------------------ planner with codecs
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_joint_planner_parity_every_config(arch):
    """search_vec's codec axis must return the identical (split, codec) to
    the scalar search_joint oracle for every registered config across a
    bandwidth sweep and budgets."""
    g = build_graph(get_config(arch), W)
    for budget in (None, 12e9):
        res = search_vec(g, ORIN, A100, BWS, cloud_budget_bytes=budget,
                         input_bytes=W.input_bytes, rtt_s=0.005, codecs=AXIS)
        for j, bw in enumerate(BWS):
            seg = search_joint(g, ORIN, A100, float(bw), AXIS,
                               cloud_budget_bytes=budget,
                               input_bytes=W.input_bytes, rtt_s=0.005)
            assert int(res.splits[j]) == seg.split, (arch, budget, bw)
            assert res.codec_names[res.codec_idx[j]] == seg.codec
            assert res.total_s[j] == pytest.approx(seg.total_s, rel=1e-12)


def test_sweep_search_codec_axis_matches_search_vec():
    graphs = {k: build_graph(get_config(k), W) for k in sorted(ARCHS)}
    sw = sweep_search(graphs, ORIN, A100, BWS, input_bytes=W.input_bytes,
                      codecs=AXIS)
    for k, g in graphs.items():
        one = search_vec(g, ORIN, A100, BWS, input_bytes=W.input_bytes,
                         codecs=AXIS)
        assert np.array_equal(sw[k].splits, one.splits), k
        assert np.array_equal(sw[k].codec_idx, one.codec_idx), k
        np.testing.assert_allclose(sw[k].total_s, one.total_s, rtol=1e-12)


def test_identity_axis_reproduces_codec_free_plan():
    g = build_graph(get_config("openvla-7b"), W)
    a = search_vec(g, ORIN, A100, BWS, input_bytes=W.input_bytes)
    b = search_vec(g, ORIN, A100, BWS, input_bytes=W.input_bytes,
                   codecs=("identity",))
    assert np.array_equal(a.splits, b.splits)
    np.testing.assert_array_equal(a.total_s, b.total_s)


def test_codec_shifts_optimal_split():
    """The motivating wart: compression changes the ranking of cut points,
    so planning on raw bytes picks a different (worse) split."""
    g = build_graph(get_config("openvla-7b"), W)
    raw = search_vec(g, ORIN, A100, BWS, input_bytes=W.input_bytes)
    joint = search_vec(g, ORIN, A100, BWS, input_bytes=W.input_bytes,
                       codecs=AXIS)
    assert np.any(raw.splits != joint.splits)
    # and the joint plan is never worse than the raw plan anywhere
    for j, bw in enumerate(BWS):
        seg = search(g, ORIN, A100, float(bw), input_bytes=W.input_bytes)
        assert joint.total_s[j] <= seg.total_s + 1e-15


def test_graph_arrays_codec_latency_matches_evaluate_split():
    g = build_graph(get_config("cogact-7b"), W)
    ga = graph_arrays(g, ORIN, A100, input_bytes=W.input_bytes)
    int4 = CODECS["int4"]
    for s in (0, 1, len(g) // 2, len(g)):
        ref = evaluate_split(g, s, ORIN, A100, 2e6, rtt_s=0.005,
                             input_bytes=W.input_bytes, codec=int4)
        got = ga.latency(s, 2e6, 0.005, codec=int4)
        assert got == pytest.approx(ref, rel=1e-12)


def test_search_accepts_codec_names():
    g = build_graph(get_config("llama3.2-3b"), W)
    a = search(g, ORIN, A100, 2e6, codec="int8")
    b = search(g, ORIN, A100, 2e6, codec=get_codec("int8"))
    assert a == b and a.codec == "int8"


# ------------------------------------------------- joint split×codec adjust
def _dit_pool():
    g = build_graph(get_config("cogact-7b"), Workload(decode_steps=0))
    first_dit = next(i for i, c in enumerate(g) if c.kind == "dit")
    return g, build_pool(g, first_dit), first_dit


def test_adjust_joint_move_on_bandwidth_drop():
    """A predicted bandwidth drop triggers a JOINT move: the decision both
    relocates the split (to the llm→dit boundary, the min-transfer layer)
    and compresses harder than identity — neither alone is optimal."""
    from repro.core import Pool
    g, _, first_dit = _dit_pool()
    # truncate the pool short of n so every candidate split ships bytes
    pool = Pool(start=first_dit, end=first_dit + 3, bytes=0.0,
                overhead_frac=0.0)
    cur = first_dit + 2
    thr = Thresholds(high=2e6, low=-2e6)
    dec = adjust(g, pool, cur, 1e6, 10e6, thr,
                 codecs=("identity", "int8", "int4"),
                 current_codec="identity", edge=ORIN, cloud=A100)
    assert dec.reason == "down" and dec.moved
    assert dec.codec in ("int8", "int4")
    assert dec.split != cur                  # split moved AND codec changed
    # the chosen pair minimises predicted transport seconds over the pool
    best = min(transport_s(cut_bytes(g, s), 1e6, get_codec(c), ORIN, A100)
               for s in pool.splits() for c in ("identity", "int8", "int4"))
    got = transport_s(cut_bytes(g, dec.split), 1e6, get_codec(dec.codec),
                      ORIN, A100)
    assert got == pytest.approx(best, rel=1e-12)


def test_adjust_up_relaxes_to_lossless():
    g, pool, cur = _dit_pool()
    thr = Thresholds(high=2e6, low=-2e6)
    dec = adjust(g, pool, cur, 20e6, 10e6, thr,
                 codecs=("identity", "int8", "int4"), current_codec="int4",
                 edge=ORIN, cloud=A100)
    assert dec.reason == "up" and dec.codec == "identity"
    vols = [cut_bytes(g, s) for s in pool.splits()]
    assert dec.split == list(pool.splits())[int(np.argmax(vols))]


def test_adjust_without_codecs_unchanged():
    g, pool, cur = _dit_pool()
    thr = Thresholds(high=2e6, low=-2e6)
    dec = adjust(g, pool, cur, 1e6, 10e6, thr)
    vols = [cut_bytes(g, s) for s in pool.splits()]
    assert dec.codec is None
    assert dec.split == list(pool.splits())[int(np.argmin(vols))]


def test_adjust_hold_keeps_codec():
    g, pool, cur = _dit_pool()
    thr = Thresholds(high=2e6, low=-2e6)
    dec = adjust(g, pool, cur, 10.5e6, 10e6, thr,
                 codecs=("identity", "int8"), current_codec="int8")
    assert dec.reason == "hold" and not dec.moved and dec.codec == "int8"


# ------------------------------------------------------- controller + fleet
def test_controller_codec_plans_different_split():
    """The controller's Alg. 1 must SEE the codec: with int4 the planned
    split differs from the raw-byte plan at constrained bandwidth (the bug
    the shared Codec path fixes — the old hard-coded int8 discount was
    applied after the split was already chosen)."""
    from repro.core import RoboECC
    cfg = get_config("openvla-7b")
    raw = RoboECC(cfg, ORIN, A100, cloud_budget_bytes=12.1e9,
                  nominal_bw_bps=0.2e6)
    c4 = RoboECC(cfg, ORIN, A100, cloud_budget_bytes=12.1e9,
                 nominal_bw_bps=0.2e6, codec="int4")
    assert raw.split != c4.split
    e, c, t_raw = raw.latency_at(raw.split, 0.2e6)
    e4, c4_, t4 = c4.latency_at(c4.split, 0.2e6)
    assert e4 + c4_ + t4 < e + c + t_raw


def test_controller_adjust_resolves_custom_codec_instances():
    """tick() must resolve the adjuster's decision within adjust_codecs —
    a registry lookup would KeyError on custom Codec instances."""
    from repro.core import (NetworkSim, PredictorConfig, RoboECC,
                            generate_trace)
    from repro.core.codec import Codec
    custom = Codec("mycodec", bytes_per_elem=0.25)
    cfg = get_config("openvla-7b")
    ctl = RoboECC(cfg, ORIN, A100, cloud_budget_bytes=12.1e9,
                  thresholds=Thresholds(high=1e3, low=-1e3),
                  adjust_codecs=[custom, "identity"])
    trace = generate_trace(1200, seed=2)
    ctl.fit_predictor(trace[:1000], PredictorConfig(epochs=5))
    net = NetworkSim(trace[1000:])
    net.step(40)
    seen = {ctl.tick(net).codec for _ in range(20)}
    assert "mycodec" in seen           # custom instance round-tripped
    assert ctl.codec in (custom, get_codec("identity"), None) or \
        ctl.codec.name in ("mycodec", "identity")


def test_encode_activation_rejects_unknown_codec():
    from repro.runtime.partition import encode_activation
    x = jnp.ones((1, 4, 256), jnp.bfloat16)
    with pytest.raises(ValueError):
        encode_activation(x, "topk")   # planner codec with no data plane
    with pytest.raises(ValueError):
        encode_activation(x, "int8x")  # typo must not silently ship int8
    with pytest.raises(ValueError):
        encode_activation(jnp.ones((1, 4, 384), jnp.bfloat16), "int4")
    assert "q4" in encode_activation(x, "int4")
    assert "q" in encode_activation(x, "int8")
    assert "x" in encode_activation(x, "")


@pytest.mark.parametrize("wire", [True, False, None, "fp8"])
def test_split_executor_refuses_a_codec_that_is_not_a_name(wire):
    """The wire format is a codec name: a flag or a planner codec with no
    data plane is refused by the plan, for either cut, and by the
    encoder, not shipped as some format of its own."""
    from repro.runtime.partition import SplitPlan, encode_activation
    x = jnp.ones((1, 4, 256), jnp.bfloat16)
    with pytest.raises(ValueError, match="no data-plane codec"):
        encode_activation(x, wire)
    with pytest.raises(ValueError, match="no data-plane codec"):
        SplitPlan(0, 2, codec=wire)
    with pytest.raises(ValueError, match="no data-plane codec"):
        SplitPlan(0, 2, codec="int8", pool2_start=2, pool2_end=3,
                  codec2=wire)


def test_fleet_codec_axis_beats_identity_on_slow_links():
    """Acceptance: int8/int4 fleet p95 beats identity at ≤ 2 MB/s."""
    from repro.core import TraceConfig
    from repro.runtime.fleet import FleetConfig, run_fleet
    trace = TraceConfig(mean_bps=2e6, bad_bps=0.5e6)
    base = dict(n_robots=16, n_ticks=200, n_replicas=3, seed=0,
                archs=("openvla-7b", "cogact-7b", "llama3.2-3b", "glm4-9b"),
                trace=trace, nominal_bw_bps=2e6)
    reps = {c: run_fleet(FleetConfig(codecs=(c,), **base))
            for c in ("identity", "int8", "int4")}
    assert reps["int8"].fleet_p95_s < reps["identity"].fleet_p95_s
    assert reps["int4"].fleet_p95_s < reps["identity"].fleet_p95_s
    # compression also lets the closed-loop fleet complete more requests
    assert reps["int4"].n_requests >= reps["identity"].n_requests
    for rep in reps.values():
        assert rep.n_requests > 0


def test_fleet_identity_default_unchanged_and_deterministic():
    from repro.runtime.fleet import FleetConfig, run_fleet
    cfg = FleetConfig(n_robots=6, n_ticks=40, n_replicas=2, seed=7,
                      archs=("openvla-7b", "cogact-7b"))
    a, b = run_fleet(cfg), run_fleet(cfg)
    assert a == b
    assert all(r.codec == "identity" for r in a.robots)
    assert a.n_codec_switches == 0


# ---------------------------------------------------------- temporal delta
def test_delta_codec_cycle_average_pricing():
    """The DeltaCodec's cost fields must be the exact cycle average over
    one resync period: one key frame (full base payload) amortised over
    ``R`` frames plus ``R-1`` delta frames (changed rows + mask)."""
    base = CODECS["int8"]
    p, R, tau = 0.1, 8, 0.02
    d = make_delta_codec(base=base, change_frac=p, resync_every=R,
                         threshold=tau)
    mask_bpe = 1.0 / (8.0 * d.row_elems)
    delta_bpe = p * base.bytes_per_elem + mask_bpe
    want = (base.bytes_per_elem + (R - 1) * delta_bpe) / R
    assert d.bytes_per_elem == pytest.approx(want, rel=1e-12)
    assert d.err_bound == pytest.approx(base.err_bound + (R - 1) * tau)
    assert d.wire_factor < base.wire_factor   # the whole point
    assert isinstance(CODECS["delta"], DeltaCodec)


@pytest.mark.parametrize("kw", [dict(resync_every=1),
                                dict(change_frac=1.0)])
def test_delta_degenerate_matches_base_bitwise(kw):
    """R=1 (key frame every step) and change fraction 1.0 (deltas never
    beat a key frame) must reproduce the base codec's pricing EXACTLY —
    same planner plan, bit-for-bit, on every config."""
    base = CODECS["int8"]
    d = make_delta_codec(base=base, **kw)
    for f in ("bytes_per_elem", "raw_bytes_per_elem", "enc_flops_per_elem",
              "enc_move_bytes_per_elem", "dec_flops_per_elem",
              "dec_move_bytes_per_elem"):
        assert getattr(d, f) == getattr(base, f), f    # exact, not approx
    for arch in sorted(ARCHS):
        g = build_graph(get_config(arch), W)
        a = search_vec(g, ORIN, A100, BWS, input_bytes=W.input_bytes,
                       codecs=("identity", d))
        b = search_vec(g, ORIN, A100, BWS, input_bytes=W.input_bytes,
                       codecs=("identity", base))
        assert np.array_equal(a.splits, b.splits), arch
        assert np.array_equal(a.codec_idx, b.codec_idx), arch
        np.testing.assert_array_equal(a.total_s, b.total_s)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_joint_planner_parity_with_delta(arch):
    """Scalar search_joint oracle vs vectorized codec axis, with the
    delta codec in the axis, on every registered config."""
    axis = AXIS + ("delta",)
    g = build_graph(get_config(arch), W)
    res = search_vec(g, ORIN, A100, BWS, input_bytes=W.input_bytes,
                     rtt_s=0.005, codecs=axis)
    for j, bw in enumerate(BWS):
        seg = search_joint(g, ORIN, A100, float(bw), axis,
                           input_bytes=W.input_bytes, rtt_s=0.005)
        assert int(res.splits[j]) == seg.split, (arch, bw)
        assert res.codec_names[res.codec_idx[j]] == seg.codec
        assert res.total_s[j] == pytest.approx(seg.total_s, rel=1e-12)


def _delta_frames(seed, n_frames, frac, S=16, D=256):
    """A frame sequence where roughly ``frac`` of token rows move
    per step (the rest are bit-identical to the previous frame)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(0.0, 1.0, (1, S, D)).astype(np.float32)
    out = [x]
    for _ in range(n_frames - 1):
        x = x.copy()
        rows = rng.random(S) < frac
        x[0, rows, :] += rng.normal(0.0, 0.5, (int(rows.sum()), D)) \
            .astype(np.float32)
        out.append(x)
    return out


def _check_delta_stream(frames, threshold, R, base="int8"):
    """Drive ``delta_encode`` over a frame sequence and assert the full
    contract: key frames byte-identical to the plain codec path, exact
    wire-byte accounting, bounded error between key frames, and the
    resync cadence."""
    from repro.runtime.partition import (decode_activation, delta_decode,
                                         delta_encode, encode_activation,
                                         payload_bytes)
    base_err = CODECS[base].err_bound if base else 0.0
    ref, ssk = None, 0
    for step, xf in enumerate(frames):
        x = jnp.asarray(xf, jnp.float32)
        payload, new_ref, is_key = delta_encode(
            x, base, ref, threshold=threshold, resync_every=R,
            steps_since_key=ssk)
        S = x.shape[1]
        if is_key:
            # key frames are byte-identical to the non-delta path
            plain = encode_activation(x, base)
            assert payload.keys() == plain.keys()
            for k in payload:
                assert np.array_equal(np.asarray(payload[k]),
                                      np.asarray(plain[k])), k
            assert payload_bytes(payload) == payload_bytes(plain)
            np.testing.assert_array_equal(
                np.asarray(new_ref), np.asarray(decode_activation(
                    plain, jnp.float32)))
            ssk = 0
        else:
            # wire bytes exact to the byte: packed mask + changed rows
            mask = payload["mask"]
            changed = np.unpackbits(mask)[:S].astype(bool)
            idx = np.flatnonzero(changed)
            body = encode_activation(x[:, idx, :], base)
            assert payload_bytes(payload) == \
                mask.nbytes + payload_bytes(body)
            ssk += 1
        recon = np.asarray(delta_decode(payload, ref, jnp.float32))
        amax = float(np.abs(xf).max())
        tol = (base_err + (0.0 if is_key else threshold)) * amax
        assert np.all(np.abs(recon - xf) <= tol + 1e-6), step
        np.testing.assert_array_equal(recon, np.asarray(new_ref))
        assert ssk < max(R, 1)      # cadence honoured
        ref = new_ref
    return True


def test_delta_roundtrip_seeded_sweep():
    for seed in range(4):
        _check_delta_stream(_delta_frames(seed, 7, 0.2),
                            threshold=0.05, R=3 + seed)
    # degenerate cadence: every frame is a key frame
    _check_delta_stream(_delta_frames(9, 4, 0.5), threshold=0.05, R=1)
    # fully static: only the mask ships between key frames
    _check_delta_stream([_delta_frames(1, 1, 0.0)[0]] * 5,
                        threshold=0.05, R=8)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(2, 8),
           frac=st.floats(0.0, 1.0), R=st.integers(1, 6),
           tau=st.floats(0.05, 0.3))
    def test_delta_roundtrip_property(seed, n, frac, R, tau):
        _check_delta_stream(_delta_frames(seed, n, frac),
                            threshold=tau, R=R)


def test_delta_transport_eviction_forces_resync():
    """Evicting a robot's cloud-side reference (budget pressure) must
    force its next frame back to a key frame."""
    from repro.runtime.partition import DeltaTransport
    frames = _delta_frames(3, 6, 0.1)
    ref_bytes = frames[0].size * 4           # float32 reference
    tr = DeltaTransport("int8", threshold=0.05, resync_every=100,
                        budget_bytes=1.5 * ref_bytes)
    _, _, k0 = tr.step(0, jnp.asarray(frames[0]))
    _, _, k1 = tr.step(0, jnp.asarray(frames[1]))
    assert k0 and not k1
    tr.step(1, jnp.asarray(frames[2]))       # robot 1 evicts robot 0
    assert tr.n_evictions >= 1
    _, _, k3 = tr.step(0, jnp.asarray(frames[3]))
    assert k3                                # reference gone → key frame
    # explicit evict has the same effect
    tr2 = DeltaTransport("int8", threshold=0.05, resync_every=100)
    tr2.step(5, jnp.asarray(frames[0]))
    _, _, kk = tr2.step(5, jnp.asarray(frames[1]))
    assert not kk
    tr2.evict(5)
    _, _, kk = tr2.step(5, jnp.asarray(frames[2]))
    assert kk


def test_controller_observe_change_frac_replans():
    """Measured change-fraction drift beyond tolerance must rebuild the
    delta codec around the measured fraction and replan; small drift and
    non-delta codecs are no-ops."""
    from repro.core import RoboECC
    cfg = get_config("openvla-7b")
    d0 = make_delta_codec(change_frac=0.15)
    ctl = RoboECC(cfg, ORIN, A100, cloud_budget_bytes=12.1e9,
                  nominal_bw_bps=1e6, codec=d0,
                  adjust_codecs=[d0, "identity"])
    assert not ctl.observe_change_frac(0.16, nominal_bw_bps=1e6)
    assert ctl.codec.change_frac == 0.15
    assert ctl.observe_change_frac(0.9, nominal_bw_bps=1e6)
    assert ctl.codec.change_frac == 0.9
    assert any(isinstance(c, DeltaCodec) and c.change_frac == 0.9
               for c in ctl.adjust_codecs)
    plain = RoboECC(cfg, ORIN, A100, cloud_budget_bytes=12.1e9,
                    nominal_bw_bps=1e6, codec="int8")
    assert not plain.observe_change_frac(0.9, nominal_bw_bps=1e6)


def test_fleet_static_scene_delta_beats_int4_bytes():
    """Acceptance direction: on a static scene the delta codec ships far
    fewer measured wire bytes than int4 under identical placements; on a
    dynamic scene the advantage collapses (the honest negative)."""
    from repro.runtime.fleet import FleetConfig, run_fleet
    d = make_delta_codec(change_frac=0.02, resync_every=16, name="delta")
    base = dict(n_robots=8, n_ticks=120, seed=3, archs=("openvla-7b",),
                continuous=True)

    def bytes_for(codec, scene):
        rep = run_fleet(FleetConfig(**base, codecs=("identity", codec),
                                    scene=scene))
        assert rep.total_wire_bytes > 0
        return rep.total_wire_bytes, rep

    b_delta, rd = bytes_for(d, "static")
    b_int4, _ = bytes_for("int4", "static")
    assert b_delta * 4 < b_int4            # ≥4× fewer bytes on-wire
    assert rd.n_delta_frames > rd.n_keyframes
    b_dyn, rdyn = bytes_for(d, "dynamic")
    b_int4_dyn, _ = bytes_for("int4", "dynamic")
    assert b_dyn > 3 * b_delta             # the advantage collapses…
    assert b_dyn > b_int4_dyn              # …to worse than plain int4
    assert rdyn.n_keyframes > 0            # ceiling frames force resyncs


def test_fleet_joint_codecs_outage_recovery_consistent():
    """replan() after a full outage must restore a codec-consistent split
    (controllers plan with the same codec the plan table chose)."""
    from repro.runtime.fleet import FleetConfig, FleetSimulator, outage_schedule
    cfg = FleetConfig(n_robots=6, n_ticks=60, n_replicas=2, seed=3,
                      archs=("openvla-7b", "cogact-7b", "llama3.2-3b"),
                      codecs=("identity", "int8", "int4"))
    cfg.replica_events = outage_schedule(cfg)
    sim = FleetSimulator(cfg)
    initial = [ctl.split for ctl in sim.controllers]
    rep = sim.run()
    assert rep.n_replans == 2 * cfg.n_robots
    for ctl, s0 in zip(sim.controllers, initial):
        assert ctl.split == s0
