"""Config registry: presence, analytic param counts, shape applicability."""
import pytest

from repro.configs import ARCHS, ASSIGNED, SHAPES, get_config, get_shape, \
    shape_applicable

EXPECTED_PARAMS_B = {
    # analytic count sanity bands (embed+head included, hence some slack)
    "llama3.2-3b": (2.5, 4.5),
    "command-r-35b": (30.0, 40.0),
    "glm4-9b": (8.0, 11.0),
    "phi3-mini-3.8b": (3.2, 4.5),
    "deepseek-v2-lite-16b": (13.0, 18.0),
    "granite-moe-3b-a800m": (2.0, 4.0),
    "mamba2-1.3b": (1.0, 1.7),
    "seamless-m4t-large-v2": (1.4, 2.9),
    "llama-3.2-vision-11b": (9.0, 13.0),
    "zamba2-1.2b": (0.9, 1.6),
    "kimi-vl-a3b-vla": (15.5, 17.5),      # Kimi-VL-A3B: 16B-A2.8B
}


def test_all_assigned_present():
    assert len(ASSIGNED) == 10
    assert "openvla-7b" in ARCHS and "cogact-7b" in ARCHS


@pytest.mark.parametrize("arch", list(EXPECTED_PARAMS_B))
def test_param_counts(arch):
    lo, hi = EXPECTED_PARAMS_B[arch]
    n = get_config(arch).n_params() / 1e9
    assert lo <= n <= hi, f"{arch}: {n:.2f}B not in [{lo},{hi}]"


def test_40_cells():
    cells = [(a, s.name) for a in ASSIGNED for s in SHAPES]
    assert len(cells) == 40
    runnable = [c for c in cells
                if shape_applicable(get_config(c[0]), get_shape(c[1]))[0]]
    skipped = [c for c in cells if c not in runnable]
    # long_500k skipped exactly for the 8 non-sub-quadratic archs
    assert len(skipped) == 8
    assert all(s == "long_500k" for _, s in skipped)
    assert {a for a, _ in skipped} == {
        "llama3.2-3b", "command-r-35b", "glm4-9b", "phi3-mini-3.8b",
        "deepseek-v2-lite-16b", "granite-moe-3b-a800m",
        "seamless-m4t-large-v2", "llama-3.2-vision-11b"}


def test_sub_quadratic_run_long():
    for arch in ("mamba2-1.3b", "zamba2-1.2b"):
        ok, _ = shape_applicable(get_config(arch), get_shape("long_500k"))
        assert ok


def test_reduced_configs_small():
    for arch in ASSIGNED:
        r = get_config(arch).reduced()
        assert r.n_params() < 50e6
