"""HLO collective parser: validated against a real compiled SPMD program."""
import re

import pytest

from repro.launch.hlo_analysis import (_parse_trip_count, _shape_bytes,
                                       loop_stack_readers, loop_weight_copies,
                                       parse_collectives, summarize)


def test_shape_bytes():
    assert _shape_bytes("bf16[8,128,512]") == 8 * 128 * 512 * 2
    assert _shape_bytes("(f32[4,4], bf16[2,2])") == 64 + 8
    assert _shape_bytes("u32[]") == 0 or _shape_bytes("u32[]") == 4  # scalar


SAMPLE = """
HloModule jit_f

%fused (p: f32[8]) -> f32[8] {
  ROOT %x = f32[8] parameter(0)
}

%body (p: (s32[], f32[16])) -> (s32[], f32[16]) {
  %ar.1 = f32[16]{0} all-reduce(%gte), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%sum
  ROOT %t = (s32[], f32[16]) tuple(%c, %ar.1)
}

%cond (p: (s32[], f32[16])) -> pred[] {
  %i = s32[] get-tuple-element(%p), index=0
  %k = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

ENTRY %main (a: f32[128,256]) -> f32[128,256] {
  %ag = bf16[128,256]{1,0} all-gather(%a0), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %ar = f32[128,256]{1,0} all-reduce(%a1), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%sum
  %w = (s32[], f32[16]) while(%init), condition=%cond, body=%body
  %cp = bf16[64,64]{1,0} collective-permute(%a2), channel_id=4, source_target_pairs={{0,1},{1,0}}
  ROOT %r = f32[128,256] add(%ar, %ar)
}
"""


def test_parse_collectives_sample():
    ops = parse_collectives(SAMPLE)
    kinds = sorted(o.kind for o in ops)
    assert kinds == ["all-gather", "all-reduce", "all-reduce",
                     "collective-permute"]
    ag = next(o for o in ops if o.kind == "all-gather")
    assert ag.group_size == 4
    assert ag.bytes == 128 * 256 * 2
    assert ag.wire_bytes == pytest.approx(0.75 * 128 * 256 * 2)
    ar = [o for o in ops if o.kind == "all-reduce"]
    big = next(o for o in ar if o.bytes == 128 * 256 * 4)
    assert big.group_size == 8
    assert big.wire_bytes == pytest.approx(2 * 7 / 8 * 128 * 256 * 4)
    # the while-body all-reduce got multiplied by trip count 7
    loop = next(o for o in ar if o.bytes == 64)
    assert loop.count == 7


def test_trip_count_parse():
    assert _parse_trip_count(SAMPLE, "cond") == 7


def test_bf16_equivalence_discount():
    # >=1MB f32 collectives are halved for the TPU roofline
    big = ("%ar = f32[1024,1024]{1,0} all-reduce(%x), channel_id=1, "
           "replica_groups={{0,1}}, to_apply=%sum\n")
    ops = parse_collectives(big)
    assert ops[0].wire_bytes_bf16 == pytest.approx(ops[0].wire_bytes / 2)


def test_real_compiled_program():
    import jax
    import jax.numpy as jnp

    def f(x):
        return jnp.sum(x * 2)

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    ops = parse_collectives(c.as_text())
    assert ops == []  # single-device: no collectives
    s = summarize(ops)
    assert s["total_wire_bytes_per_device"] == 0


# A layer loop over stacks of four 8x8 weights: ``wq`` is sliced out and
# relayouted before its dot, ``wo`` is read in place by its dot.  ``a`` is
# an activation the size of a layer that no weight flows into.
LOOP = """
HloModule jit_loop

%body (p: (s32[], bf16[4,8,8], bf16[4,8,8], bf16[8,8])) -> (s32[], bf16[4,8,8], bf16[4,8,8], bf16[8,8]) {
  %p = (s32[], bf16[4,8,8]{2,1,0:T(8,128)(2,1)}, bf16[4,8,8]{2,1,0}, /*index=3*/bf16[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %wq = bf16[4,8,8]{2,1,0:T(8,128)(2,1)} get-tuple-element(%p), index=1
  %wo = bf16[4,8,8]{2,1,0} get-tuple-element(%p), index=2
  %x = bf16[8,8]{1,0} get-tuple-element(%p), index=3
  %a = bf16[8,8]{1,0} add(%x, %x)
  %s = bf16[1,8,8]{2,1,0:T(8,128)(2,1)S(1)} fusion(%wq, %i), kind=kLoop, calls=%slice
  %c = bf16[1,8,8]{1,2,0} copy(%s)
  %b = bf16[8,8]{0,1} bitcast(%c)
  %q = bf16[8,8]{1,0} fusion(%a, %b), kind=kOutput, calls=%dot
  %o = bf16[8,8]{1,0} fusion(%q, %wo, %i), kind=kOutput, calls=%dot
  ROOT %t = (s32[], bf16[4,8,8]{2,1,0}, bf16[4,8,8]{2,1,0}, bf16[8,8]{1,0}) tuple(%i, %wq, %wo, %o)
}

%cond (p: (s32[], bf16[4,8,8], bf16[4,8,8], bf16[8,8])) -> pred[] {
  %i = s32[] get-tuple-element(%p), index=0
  %k = s32[] constant(4)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

ENTRY %main (w: bf16[4,8,8]) -> bf16[8,8] {
  %s = bf16[1,8,8]{2,1,0} fusion(%w0, %z), kind=kLoop, calls=%slice
  %w = (s32[], bf16[4,8,8], bf16[4,8,8], bf16[8,8]) while(%init), condition=%cond, body=%body
  ROOT %r = bf16[8,8]{1,0} get-tuple-element(%w), index=3
}
"""


def test_loop_weight_copies_follow_the_stacks():
    # the slice and its relayout count; the bitcast, the dots, the
    # activation of a layer's size and the slice outside the loop do not
    assert loop_weight_copies(LOOP, [(4, 8, 8)] * 2) == ["s", "c"]
    assert loop_weight_copies(LOOP, [(4, 8, 16)]) == []


def test_loop_stack_readers_name_each_kind():
    assert loop_stack_readers(LOOP, [(4, 8, 8)] * 2) == [("s", "kLoop"),
                                                         ("o", "kOutput")]
