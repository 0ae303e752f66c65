"""Structure model (Eq.1) + hardware model (Eq.2) invariants."""
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs import ASSIGNED, get_config
from repro.core import (Workload, build_graph, fit_eta, layer_latency,
                        roofline, stack_latency, total_flops,
                        total_weight_bytes)
from repro.core.hardware import A100, ORIN, THOR, TPU_V5E, DeviceSpec
from repro.core.structure import LayerCost


@pytest.mark.parametrize("arch", list(ASSIGNED) + ["openvla-7b", "cogact-7b"])
def test_graph_wellformed(arch):
    cfg = get_config(arch)
    g = build_graph(cfg, Workload())
    assert len(g) >= cfg.n_layers
    assert all(c.flops >= 0 for c in g)
    assert all(c.weight_bytes >= 0 for c in g)
    assert all(c.datamove_bytes > 0 for c in g)
    # weight bytes consistent with the config's analytic param count
    wb = total_weight_bytes(g)
    n = cfg.n_params() * Workload().wbytes
    assert 0.5 * n <= wb <= 1.3 * n


def test_dit_layers_carry_repeat():
    g = build_graph(get_config("cogact-7b"), Workload(decode_steps=0))
    dits = [c for c in g if c.kind == "dit"]
    assert len(dits) == 12
    assert all(c.repeat == 10 for c in dits)
    llm = [c for c in g if c.kind == "llm"]
    # a DiT layer is tiny by weights but repeated 10x in compute & transfer
    assert dits[0].weight_bytes < llm[0].weight_bytes
    assert dits[0].out_transfer_bytes > 0


def test_moe_graph_heterogeneity():
    g = build_graph(get_config("deepseek-v2-lite-16b"), Workload())
    kinds = [c.kind for c in g]
    assert "moe" in kinds and "llm" in kinds  # first dense layer vs moe


def test_eq2_roofline_shape():
    c = LayerCost("l", "llm", flops=1e12, weight_bytes=1e9,
                  datamove_bytes=1e9, out_transfer_bytes=1e5)
    t_orin = layer_latency(c, ORIN)
    # compute-bound on Orin at eta 0.3: 1e12/(275e12*0.3) vs 1e9/(204.8e9*0.6)
    assert t_orin == pytest.approx(max(1e12 / (275e12 * 0.3),
                                       1e9 / (204.8e9 * 0.6)))
    t_a100 = layer_latency(c, A100)
    assert t_a100 < t_orin


def test_fit_eta_hits_target():
    g = build_graph(get_config("openvla-7b"), Workload())
    dev = fit_eta(g, ORIN, target_s=1.1194)
    assert stack_latency(g, dev) == pytest.approx(1.1194, rel=1e-6)


def test_roofline_terms():
    t = roofline(hlo_flops=197e12 * 256, hlo_bytes=819e9 * 256,
                 collective_bytes=50e9 * 256, n_chips=256, dev=TPU_V5E)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(1.0)
    assert t.collective_s == pytest.approx(1.0)
    assert t.bound_s == 1.0


@given(st.floats(1e9, 1e15), st.floats(1e6, 1e12), st.floats(0, 1e12))
@settings(max_examples=30, deadline=None)
def test_roofline_dominant_consistent(f, b, c):
    t = roofline(f, b, c, 256, TPU_V5E)
    assert t.bound_s == max(t.compute_s, t.memory_s, t.collective_s)
    assert t.dominant in ("compute", "memory", "collective")


def test_decode_steps_increase_datamove():
    cfg = get_config("openvla-7b")
    g0 = build_graph(cfg, Workload(decode_steps=0))
    g7 = build_graph(cfg, Workload(decode_steps=7))
    llm0 = next(c for c in g0 if c.kind == "llm")
    llm7 = next(c for c in g7 if c.kind == "llm")
    assert llm7.datamove_bytes > 5 * llm0.datamove_bytes  # weight re-reads


def test_chip_peaks_keyed_by_device_kind():
    from repro.core.hardware import CHIP_PEAKS, chip_peaks
    assert chip_peaks("TPU v5 lite") is TPU_V5E
    assert set(CHIP_PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError, match="TPU v9"):
        chip_peaks("TPU v9")
