"""A run with the timed path broken underneath comes out not correct: a
served token or action altered where it is produced (the fault a batch-1
serving cell can have), and the controls, the reference in int8 and in
fp8 put in the program's place.  A mix whose bandwidth trace runs out
inside the window fails the run."""
import jax.numpy as jnp
import pytest

from bench_small import cells, small_run


def _alter_answer(cell):
    cloud = cell.ex._cloud

    def broken(params, payload, split, key):
        action, logits = cloud(params, payload, split, key)
        if logits is None:
            return action + 1.0, None
        rolled = jnp.roll(logits, 1, axis=-1)
        return action, rolled
    cell.ex._cloud = broken


@pytest.mark.parametrize("workload", cells())
def test_altered_answer_is_not_correct(workload):
    r = small_run(workload, fault=_alter_answer)
    assert not r["correct"], r["checks"]


# Widths at which int8's rounding error grows as it does at the published
# widths (it grows with the width a row's scale spans): at the default
# test widths int8 reads about half of what it reads on the chip.
CONTROL_MODEL = {"vit_layers": 24, "n_layers": 4, "vit_dim": 512,
                 "n_patches": 64, "d_model": 1024, "n_heads": 8,
                 "n_kv_heads": 8, "head_dim": 128, "d_ff": 2816}


@pytest.mark.parametrize("control", ["int8", "fp8"])
@pytest.mark.parametrize("workload", cells())
def test_control_is_not_correct(workload, control):
    def put_control(cell):
        cell.control = control
    r = small_run(workload, fault=put_control, model=CONTROL_MODEL)
    assert not r["correct"], r["checks"]


def test_used_up_bandwidth_trace_fails_the_run():
    import run
    workload = next(w for w in cells()
                    if run.load_cell(w)["traffic"]["controller"]["adjust"])
    with pytest.raises(RuntimeError, match="bandwidth trace"):
        small_run(workload, controller={"trace_ticks": 3040,
                                        "train_ticks": 3000})
