"""Edge/cloud partitioned execution — RoboECC's runtime artifact.

The model's layer stack is cut at a *dynamic* split index that lives inside a
static **parameter-sharing pool** ``[pool_start, pool_end)`` (paper §IV-B-2):
both tiers hold the pool layers' weights, so moving the split inside the pool
needs **no weight shipping and no recompilation** — the split index is a
traced argument, clamped into the pool on the host, and each tier runs its
blocks in one loop whose bound is the split (the edge ``[0, split)``, the
cloud ``[split, L)``).

Multi-cut placements (``core/placement.py``) add a **second pool**
``[pool2_start, pool2_end)`` around the cloud→edge tail cut of an
edge→cloud→edge plan: the cloud runs blocks ``[split, split2)`` and the
edge tail (including the final norm / LM head / action decode) runs the
rest — both cuts are traced arguments, so moving either one inside its
pool recompiles nothing.  A two-pool run ships two
payloads: the uplink cut activation (``codec``) and the downlink tail
activation (``codec2``).

Semantics: the split is fixed for the duration of one request (one VLA action
inference).  VLA workloads re-prefill every action step (the camera image
changes), so caches never need to migrate across the cut — this matches the
paper's setting, where adjustment happens between inferences.

Tracing: each tier's work runs under the ``jax.named_scope`` names in
``SCOPES``, which the compiled programs keep as ``op_name`` metadata, and
``run`` dispatches each tier under a ``roboecc/serve/*`` host span
(``jax.profiler.TraceAnnotation``).  Both show only in a profiler trace;
neither changes the compiled work, and with no profiler session a span
costs a few hundred nanoseconds of host time.

The cut activation is optionally shipped through the int8 or packed-int4
activation codec (kernels/activation_codec) — 2x / ~3.8x fewer wire bytes.
The planner-side price of each format (wire factor + encode/decode compute)
lives in ``core/codec.py``; this module is the matching data plane.

Streamed transport (``core/pipeline.py``): ``chunk_payload`` slices an
encoded payload into ``n_chunks`` token-axis chunks and ``merge_chunks``
reassembles them — the data plane of the 3-stage streaming pipeline the
planner prices as a makespan.  Both codec formats quantize per
(row, 128-block) with no cross-token state, so slicing the encoded
payload along the token axis is bit-identical to encoding each chunk
separately, and ``decode(merge(chunks)) == decode(payload)`` exactly —
the streamed forward produces bit-identical outputs to the monolithic
one (``run_streamed``).  Chunk extraction is pure shape logic outside
every jitted function: the traced edge/cloud forwards never see the
chunk count, so changing ``n_chunks`` between requests recompiles
nothing (one trace per function across all chunk counts — the same
invariant the dynamic cut indices already have).

Temporal-delta transport (``core/codec.DeltaCodec``): ``delta_encode``
ships only the token rows whose activation changed since the previous
step against a cloud-side *reference* copy, plus a packed one-bit
change mask; every R-th frame is a full key frame (byte-identical to
the plain ``encode_activation`` payload) that resyncs the reference.
``DeltaTransport`` keeps the per-robot reference cache, with bytes
accounted against an optional budget via
``runtime.kvcache.ReferenceLedger`` — an evicted robot's next frame is
forced back to a key frame.  These run host-side (the change mask is
data-dependent shape logic), outside every jitted forward.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.pipeline import chunk_sizes
from ..kernels.activation_codec import ops as codec
from ..models import moe as MOE
from ..models import transformer as T
from ..models import vla as V
from ..models.layers import embed, rmsnorm
from ..models.transformer import block_forward

Tree = Any

# ``jax.named_scope`` names around each tier's work, read back from the
# compiled programs' ``op_name`` metadata to split a tier's device time:
# the ViT, text embed and concat (VLA edge); the block loop; the cut's
# encode and decode; the final norm and action or LM head; and, inside
# the block loop of a dropless MoE trunk, the routing and the routed
# experts' dispatch, grouped matmuls and combine (``models/moe.py``).
SCOPES = ("vision", "trunk", "encode", "decode", "head", MOE.ROUTER,
          MOE.EXPERTS)
VISION, TRUNK, ENCODE, DECODE, HEAD = SCOPES[:5]


@dataclasses.dataclass(frozen=True)
class SplitPlan:
    """Static pool placement(s) + codec choice; the cut indices themselves
    are dynamic.

    ``codec``: "" (raw), "int8" or "int4" — the wire format for the uplink
    cut activation.  ``pool2_start``/``pool2_end`` (both ``-1`` =
    disabled) place the second pool of an edge→cloud→edge plan; ``codec2``
    is the downlink wire format.

    ``use_codec`` is a DEPRECATED alias for ``codec="int8"`` kept as a
    warning shim for one release — pass ``codec`` explicitly
    (``core/placement.py`` plans carry codec names per cut)."""
    pool_start: int
    pool_end: int
    use_codec: Optional[bool] = None
    codec: str = ""
    pool2_start: int = -1
    pool2_end: int = -1
    codec2: str = ""

    def __post_init__(self):
        if self.use_codec is not None:
            warnings.warn(
                "SplitPlan(use_codec=...) is deprecated; pass "
                "codec='int8' (or '') instead — use_codec will be removed "
                "next release", DeprecationWarning, stacklevel=3)
        if (self.pool2_start >= 0) != (self.pool2_end >= 0):
            raise ValueError("pool2_start and pool2_end must be set "
                             "together (or both left at -1)")
        if self.two_pool and not (self.pool_end <= self.pool2_start
                                  <= self.pool2_end):
            raise ValueError(
                f"second pool [{self.pool2_start}, {self.pool2_end}) must "
                f"follow the first [{self.pool_start}, {self.pool_end})")

    @property
    def two_pool(self) -> bool:
        return self.pool2_start >= 0

    @property
    def wire_codec(self) -> str:
        if self.codec:
            return self.codec
        return "int8" if self.use_codec else ""

    def clamp(self, split: int) -> int:
        return max(self.pool_start, min(int(split), self.pool_end))

    def clamp2(self, split2: int) -> int:
        return max(self.pool2_start, min(int(split2), self.pool2_end))


# ------------------------------------------------------------------ helpers
def _run_blocks(cfg, blocks: Tree, x: jax.Array, positions, lo, hi, *,
                is_moe: bool, counts: Optional[jax.Array] = None):
    """Run blocks ``[lo, hi)`` of the stacked ``blocks`` tree.

    One loop step per block, each indexing its layer's weights out of the
    stack; the projection dots read that layer in place, so no step
    copies a weight (``models/attention._qkv``).  Either bound
    may be traced — a dynamic cut is a loop bound, so moving it inside its
    pool recompiles nothing and no block runs on both tiers.  Given
    ``counts`` (dropless MoE blocks), the loop also carries the layers'
    dispatch counts (``models/moe.COUNTS``) and returns ``(x, counts)``.
    A dropless MoE block's grouped matmuls get the routed experts' whole
    stacks and read them at the step's layer in place (a kernel cannot
    have a slice fused into its operand, so it would be a copy)."""
    stacked = is_moe and cfg.moe_dropless

    def block(i, h):
        pl = jax.tree_util.tree_map(
            lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False),
            blocks)
        if not stacked:
            return block_forward(cfg, pl, h, positions, is_moe=is_moe)
        pl["moe"] = dict(pl["moe"], **{k: blocks["moe"][k]
                                       for k in MOE.EXPERT_WEIGHTS})
        return block_forward(cfg, pl, h, positions, is_moe=True, layer=i)

    if counts is None:
        return jax.lax.fori_loop(lo, hi, lambda i, h: block(i, h)[0], x)

    def body(i, carry):
        h, c = carry
        h, _, got = block(i, h)
        return h, MOE.add_counts(c, got)

    return jax.lax.fori_loop(lo, hi, body, (x, counts))


def _trunk_span(cfg, params: Tree, x: jax.Array, positions, lo, hi):
    """Trunk blocks ``[lo, hi)`` across the layer groups
    (``transformer._groups``); either bound may be a traced cut.  Returns
    ``(x, counts)``: the dropless MoE layers' dispatch counts
    (``models/moe.COUNTS``), zero where the span has none."""
    off, counts = 0, MOE.no_counts()
    with jax.named_scope(TRUNK):
        for name, n, is_moe in T._groups(cfg):
            a, b = _clip(lo - off, 0, n), _clip(hi - off, 0, n)
            if is_moe and cfg.moe_dropless:
                x, counts = _run_blocks(cfg, params[name], x, positions, a,
                                        b, is_moe=True, counts=counts)
            else:
                x = _run_blocks(cfg, params[name], x, positions, a, b,
                                is_moe=is_moe)
            off += n
    return x, counts


def _clip(v, lo: int, hi: int):
    """Clamp a static or traced layer index into ``[lo, hi]``."""
    if isinstance(v, int):
        return max(lo, min(v, hi))
    return jnp.clip(v, lo, hi)


def _codec_block(D: int) -> int:
    return 128 if D % 128 == 0 else D


def encode_activation(x: jax.Array, wire_codec):
    """``wire_codec``: "" / False (raw), "int8" / True, or "int4".

    int4 requires ``x.shape[-1] % 256 == 0`` (two 128-blocks pack per
    byte lane-aligned) and raises otherwise — a silent int8 fallback
    would ship ~2x the wire bytes the planner priced."""
    if not wire_codec:
        return {"x": x}
    if wire_codec == "int4":
        if x.shape[-1] % 256 != 0:
            raise ValueError(
                f"int4 codec needs last dim % 256 == 0, got {x.shape}; "
                "use int8 (and plan with the int8 codec) instead")
        with jax.named_scope(ENCODE):
            p, s = codec.quantize_int4(x)
        return {"q4": p, "s": s}
    if wire_codec not in ("int8", True):
        # refuse rather than silently ship a different format than the
        # planner priced (planner codecs like fp16/topk have no data
        # plane here yet)
        raise ValueError(f"no data-plane codec {wire_codec!r}; "
                         "have '', 'int8', 'int4'")
    with jax.named_scope(ENCODE):
        q, s = codec.quantize(x, block=_codec_block(x.shape[-1]))
    return {"q": q, "s": s}


def decode_activation(payload: Dict, dtype=jnp.bfloat16) -> jax.Array:
    if "x" in payload:
        return payload["x"]
    with jax.named_scope(DECODE):
        if "q4" in payload:
            return codec.dequantize_int4(payload["q4"], payload["s"],
                                         jnp.dtype(dtype))
        q, s = payload["q"], payload["s"]
        return codec.dequantize(q, s, jnp.dtype(dtype),
                                block=q.shape[-1] // s.shape[-1])


def payload_bytes(payload: Dict) -> int:
    return sum(v.size * v.dtype.itemsize for k, v in payload.items()
               if hasattr(v, "size"))


def chunk_payload(payload: Dict, n_chunks: int) -> List[Dict]:
    """Slice an encoded cut-activation payload into ``n_chunks`` token-axis
    chunks (``numpy.array_split`` sizing via ``core.pipeline.chunk_sizes``,
    so the planner's byte accounting and the wire slices agree).  Every
    payload array — raw ``x``, int8 ``q``, packed-int4 ``q4`` and the
    block scales ``s`` — carries tokens on axis 1 with per-row scale
    groups, so slicing commutes with the codec: shipping these chunks is
    byte-identical to encoding each token slice separately.  Chunks for
    ``n_chunks > tokens`` come out empty and merge back harmlessly."""
    S = next(iter(payload.values())).shape[1]
    out: List[Dict] = []
    start = 0
    for sz in chunk_sizes(S, n_chunks):
        out.append({k: v[:, start:start + sz] for k, v in payload.items()})
        start += sz
    return out


def merge_chunks(chunks: List[Dict]) -> Dict:
    """Reassemble ``chunk_payload`` slices.  ``decode_activation`` of the
    merged payload is bit-identical to decoding the original payload —
    concatenation of token slices is exact."""
    if not chunks:
        raise ValueError("merge_chunks needs at least one chunk")
    return {k: jnp.concatenate([c[k] for c in chunks], axis=1)
            for k in chunks[0]}


# ------------------------------------------------- temporal-delta transport
def delta_encode(x: jax.Array, base_codec: str,
                 ref: Optional[jax.Array] = None, *,
                 threshold: float = 0.02, resync_every: int = 8,
                 steps_since_key: int = 0
                 ) -> Tuple[Dict, jax.Array, bool]:
    """Encode ``x`` against the reference ``ref`` from the previous step.

    Returns ``(payload, new_ref, is_keyframe)``.  Key frames (``ref`` is
    ``None``, ``resync_every <= 1``, the resync cadence fires, or the
    delta would be at least as large as a full frame) produce a payload
    **byte-identical** to ``encode_activation(x, base_codec)`` — the
    non-delta path — and reset the reference.  Delta frames ship a
    packed one-bit change mask over the token rows (axis 1) plus the
    base-codec encoding of just the changed rows; a row counts as
    changed when ``max|x - ref|`` over that row exceeds
    ``threshold * max|x|``.  ``new_ref`` is the cloud-side
    reconstruction (``delta_decode`` of the payload) — both tiers
    update their reference from the *shipped* bytes, so they stay
    bit-identical without a second channel.

    Unsent rows satisfy ``|x - ref| <= threshold * max|x|`` at *this*
    step by construction; the planner's per-cycle bound
    ``base_err + (R-1) * threshold`` (``DeltaCodec.err_bound``) is the
    conservative envelope of that over a key-frame cycle.

    Host-side only: the change mask drives data-dependent shapes, so
    this cannot run under ``jit`` (same contract as ``chunk_payload`` —
    pure transport logic outside the traced forwards).  Unknown codec
    names are rejected by ``encode_activation`` exactly as on the
    non-delta path."""
    is_key = (ref is None or int(resync_every) <= 1
              or int(steps_since_key) + 1 >= int(resync_every))
    if not is_key:
        absmax = float(jnp.max(jnp.abs(x)))
        rowdiff = jnp.max(jnp.abs(x - ref.astype(x.dtype)), axis=(0, 2))
        changed = np.asarray(rowdiff > threshold * absmax)
        idx = np.flatnonzero(changed)
        S = x.shape[1]
        body = encode_activation(x[:, idx, :], base_codec)
        mask = np.packbits(changed)
        # encoded bytes are linear in the token count (per-row block
        # scales, no cross-token state), so the full-frame size follows
        # from the changed-rows size without encoding twice
        if idx.size and mask.nbytes + payload_bytes(body) \
                >= payload_bytes(body) * (S / idx.size):
            is_key = True       # delta no smaller than a key frame
        else:
            payload = {"mask": mask, **body}
            new_ref = delta_decode(payload, ref, x.dtype)
            return payload, new_ref, False
    payload = encode_activation(x, base_codec)
    return payload, decode_activation(payload, x.dtype), True


def delta_decode(payload: Dict, ref: Optional[jax.Array] = None,
                 dtype=jnp.bfloat16) -> jax.Array:
    """Reconstruct the full cut activation from a ``delta_encode``
    payload.  Key-frame payloads (no ``"mask"`` key) decode standalone;
    delta payloads scatter the decoded changed rows into a copy of
    ``ref``."""
    if "mask" not in payload:
        return decode_activation(payload, dtype)
    if ref is None:
        raise ValueError("delta payload needs the reference activation "
                         "(reference evicted? force a key frame)")
    S = ref.shape[1]
    changed = np.unpackbits(np.asarray(payload["mask"]),
                            count=S).astype(bool)
    idx = np.flatnonzero(changed)
    out = jnp.asarray(ref, dtype=jnp.dtype(dtype))
    if idx.size:
        body = {k: v for k, v in payload.items() if k != "mask"}
        out = out.at[:, idx, :].set(decode_activation(body, dtype))
    return out


class DeltaTransport:
    """Per-robot temporal-delta transport state.

    One instance simulates both tiers of the delta channel for a fleet:
    the per-robot reference activation (cloud-side copy the edge
    mirrors bit-exactly, since both update from the shipped bytes), the
    steps-since-keyframe counter that drives the resync cadence, and
    the ``ReferenceLedger`` byte accounting that makes references
    compete with the KV budget.  When a ``put`` overflows the budget
    the stalest robots' references are evicted and their next ``step``
    is forced onto a key frame."""

    def __init__(self, base_codec: str = "int8", *,
                 threshold: float = 0.02, resync_every: int = 8,
                 budget_bytes: Optional[float] = None):
        from .kvcache import ReferenceLedger
        self.base_codec = base_codec
        self.threshold = threshold
        self.resync_every = int(resync_every)
        self.ledger = ReferenceLedger(budget_bytes)
        self._ref: Dict[int, jax.Array] = {}
        self._ssk: Dict[int, int] = {}
        self.n_keyframes = 0
        self.n_delta_frames = 0
        self.n_evictions = 0

    def step(self, robot_id: int, x: jax.Array
             ) -> Tuple[Dict, jax.Array, bool]:
        """Encode ``x`` for ``robot_id`` and return
        ``(payload, reconstruction, is_keyframe)`` — the reconstruction
        is what the cloud decodes (and the next step's reference)."""
        payload, new_ref, is_key = delta_encode(
            x, self.base_codec, self._ref.get(robot_id),
            threshold=self.threshold, resync_every=self.resync_every,
            steps_since_key=self._ssk.get(robot_id, 0))
        self._ref[robot_id] = new_ref
        self._ssk[robot_id] = 0 if is_key else self._ssk[robot_id] + 1
        if is_key:
            self.n_keyframes += 1
        else:
            self.n_delta_frames += 1
        for k in self.ledger.put(robot_id,
                                 new_ref.size * new_ref.dtype.itemsize):
            self.evict(k)
            self.n_evictions += 1
        return payload, new_ref, is_key

    def evict(self, robot_id: int) -> None:
        """Drop ``robot_id``'s reference; its next frame is a forced
        key frame."""
        self._ref.pop(robot_id, None)
        self._ssk.pop(robot_id, None)
        self.ledger.drop(robot_id)


# ================================================================ LM executor
class LMSplitExecutor:
    """Dense/MoE decoder-only LM split at a block boundary.

    Layer indexing: 0..L-1 are transformer blocks; embed always on edge.
    Single-pool plans keep final-norm + unembed cloud-side (the paper
    segments from the last layer towards the front, keeping the output
    head cloud-side); a two-pool plan returns the tail — pool-2 layers
    with ``i >= split2``, the blocks after ``pool2_end`` and the LM head —
    to the edge, shipping a second (downlink) payload.
    """

    def __init__(self, cfg, plan: SplitPlan):
        if cfg.family not in ("dense", "moe"):
            raise ValueError(f"LMSplitExecutor runs dense/moe LMs, not "
                             f"family {cfg.family!r}")
        assert 0 <= plan.pool_start <= plan.pool_end <= cfg.n_layers
        if plan.two_pool:
            assert plan.pool_end <= plan.pool2_start \
                <= plan.pool2_end <= cfg.n_layers
        self.cfg = cfg
        self.plan = plan
        self._edge = jax.jit(self._edge_fwd)
        self._cloud = jax.jit(self._cloud_fwd)
        if plan.two_pool:
            self._cloud_mid = jax.jit(self._cloud_mid_fwd)
            self._tail = jax.jit(self._tail_fwd)

    def _span(self, params, x, positions, lo, hi):
        return _trunk_span(self.cfg, params, x, positions, lo, hi)[0]

    def _head(self, params, x):
        """Final norm + LM head."""
        with jax.named_scope(HEAD):
            return T.lm_logits(self.cfg, params, x)

    # -- edge side: embed + blocks [0, split)
    def _edge_fwd(self, params, tokens, split):
        cfg, plan = self.cfg, self.plan
        positions = jnp.arange(tokens.shape[1])
        x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
        x = self._span(params, x, positions, 0, split)
        return encode_activation(x, plan.wire_codec)

    # -- cloud side (single-pool): blocks [split, L) + head
    def _cloud_fwd(self, params, payload, split):
        cfg = self.cfg
        x = decode_activation(payload, cfg.dtype)
        positions = jnp.arange(x.shape[1])
        x = self._span(params, x, positions, split, cfg.n_layers)
        return self._head(params, x)

    # -- cloud side (two-pool): blocks [split, split2)
    def _cloud_mid_fwd(self, params, payload, split, split2):
        cfg, plan = self.cfg, self.plan
        x = decode_activation(payload, cfg.dtype)
        positions = jnp.arange(x.shape[1])
        x = self._span(params, x, positions, split, split2)
        return encode_activation(x, plan.codec2)

    # -- edge tail (two-pool): blocks [split2, L) + head
    def _tail_fwd(self, params, payload, split2):
        cfg = self.cfg
        x = decode_activation(payload, cfg.dtype)
        positions = jnp.arange(x.shape[1])
        x = self._span(params, x, positions, split2, cfg.n_layers)
        return self._head(params, x)

    # -- public API
    def run(self, params, tokens, split: int,
            split2: Optional[int] = None):
        """One co-inference, dispatched without waiting for the device.
        Single-pool plans return ``(logits, uplink_payload)``; two-pool
        plans take the second cut ``split2`` and return ``(logits, {"up":
        ..., "down": ...})`` — the logits computed on the edge tail.  The
        host spans ``roboecc/serve/edge`` and ``roboecc/serve/cloud``
        cover each tier's dispatch on the profiler's clock."""
        with TraceAnnotation("roboecc/serve/edge"):
            split = jnp.int32(self.plan.clamp(split))
            payload = self._edge(params, tokens, split)
        with TraceAnnotation("roboecc/serve/cloud"):
            if not self.plan.two_pool:
                return self._cloud(params, payload, split), payload
            split2 = jnp.int32(self.plan.clamp2(
                split2 if split2 is not None else self.plan.pool2_end))
            down = self._cloud_mid(params, payload, split, split2)
            logits = self._tail(params, down, split2)
        return logits, {"up": payload, "down": down}

    def run_streamed(self, params, tokens, split: int, n_chunks: int,
                     split2: Optional[int] = None):
        """One co-inference with the uplink payload shipped in
        ``n_chunks`` token-axis chunk slices (``chunk_payload``).  Returns
        ``(logits, chunks)`` (two-pool: ``(logits, {"up": chunks,
        "down": payload})`` — the small downlink tail never streams).
        Bit-identical to ``run``: the jitted forwards are chunk-agnostic
        (no retrace across chunk counts) and the codec slices exactly."""
        split_t = jnp.int32(self.plan.clamp(split))
        payload = self._edge(params, tokens, split_t)
        chunks = chunk_payload(payload, n_chunks)
        merged = merge_chunks(chunks)
        if not self.plan.two_pool:
            return self._cloud(params, merged, split_t), chunks
        split2_t = jnp.int32(self.plan.clamp2(
            split2 if split2 is not None else self.plan.pool2_end))
        down = self._cloud_mid(params, merged, split_t, split2_t)
        logits = self._tail(params, down, split2_t)
        return logits, {"up": chunks, "down": down}


# ================================================================ VLA executor
class VLASplitExecutor:
    """ViT + LLM (+ action head) split; pool(s) inside the LLM block range.

    Layer indexing: ViT blocks [0, Lv) — always edge-side candidates; LLM
    blocks [Lv, Lv+L); action head after.  ``core/structure.py``'s graph
    has one more node, the ViT projection, between the two stacks;
    ``launch/serve.executor_index`` maps a graph split onto this indexing.
    The dynamic pools must lie inside the LLM range; the ViT boundary is a
    static placement choice evaluated by the cost model (DESIGN.md §7).

    A two-pool plan realizes the edge→cloud→edge placement: the cloud runs
    the trunk up to the (dynamic) second cut and ships the tail activation
    back; the final norm + action decode run on the **edge** — ActionFlow's
    action-stage-on-edge pattern, priced by
    ``core/segmentation.search_multicut``.
    """

    def __init__(self, cfg, plan: SplitPlan, action_on_cloud: bool = True):
        if cfg.family != "vla":
            raise ValueError(f"VLASplitExecutor runs VLA models, not "
                             f"family {cfg.family!r}")
        self.cfg = cfg
        self.plan = plan
        Lv = cfg.vit_layers
        assert Lv <= plan.pool_start <= plan.pool_end <= Lv + cfg.n_layers
        if plan.two_pool:
            assert plan.pool_end <= plan.pool2_start \
                <= plan.pool2_end <= Lv + cfg.n_layers
        self.action_on_cloud = action_on_cloud and not plan.two_pool
        self._edge = jax.jit(self._edge_fwd)
        self._cloud = jax.jit(self._cloud_fwd)
        # the same programs with the trunk's dispatch counts as a further
        # output, compiled on the first call that asks for them
        self._edge_counted = jax.jit(self._edge_counts)
        self._cloud_counted = jax.jit(self._cloud_counts)
        if plan.two_pool:
            self._cloud_mid = jax.jit(self._cloud_mid_fwd)
            self._tail = jax.jit(self._tail_fwd)

    def _span_counts(self, params, x, positions, lo, hi):
        """LLM blocks ``[lo, hi)`` in graph indexing (``_trunk_span``)."""
        Lv = self.cfg.vit_layers
        return _trunk_span(self.cfg, params, x, positions, lo - Lv, hi - Lv)

    def _span(self, params, x, positions, lo, hi):
        return self._span_counts(params, x, positions, lo, hi)[0]

    def _tail_slice(self) -> int:
        """Static downlink sequence length.  When pool 2 is degenerate at
        the graph end the tail is exactly the action stage, which reads
        only its semantic conditioning slice (detok: the last
        ``action_dim`` positions; DiT/MLP/LSTM: the cognition token) — the
        bytes the planner prices via ``LayerCost.in_transfer_bytes``.  A
        pool 2 with movable blocks needs the full sequence (and the
        planner prices those mid-trunk cuts at full activation too).
        0 means "ship everything"."""
        cfg, plan = self.cfg, self.plan
        if plan.pool2_start == plan.pool2_end == cfg.vit_layers \
                + cfg.n_layers:
            return cfg.action_dim if cfg.vla_action_head in ("detok", "") \
                else 1
        return 0

    def _action_decode(self, params, x, key):
        """Final norm + action decode (models.vla.vla_forward tail) — runs
        on whichever tier owns the last segment.  Returns ``(action,
        logits)``: the detok head's logits at the ``action_dim`` action
        positions, ``None`` for heads that decode no tokens."""
        cfg = self.cfg
        with jax.named_scope(HEAD):
            h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
            if cfg.vla_action_head in ("detok", ""):
                logits = V.detok_logits(cfg, params, h)
                return V.detok_action(logits), logits
            if cfg.vla_action_head == "dit":
                return V.dit_sample(cfg, params["action"], h[:, -1],
                                    key), None
        raise NotImplementedError(cfg.vla_action_head)

    def _edge_counts(self, params, patches, tokens, split):
        cfg = self.cfg
        with jax.named_scope(VISION):
            img = V.vit_encode(cfg, params["vit"], patches)
            txt = embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
            x = jnp.concatenate([img, txt], axis=1)
        positions = jnp.arange(x.shape[1])
        x, counts = self._span_counts(params, x, positions, cfg.vit_layers,
                                      split)
        return encode_activation(x, self.plan.wire_codec), counts

    def _edge_fwd(self, params, patches, tokens, split):
        return self._edge_counts(params, patches, tokens, split)[0]

    def _cloud_counts(self, params, payload, split, key):
        cfg = self.cfg
        x = decode_activation(payload, cfg.dtype)
        positions = jnp.arange(x.shape[1])
        x, counts = self._span_counts(params, x, positions, split,
                                      cfg.vit_layers + cfg.n_layers)
        return (*self._action_decode(params, x, key), counts)

    def _cloud_fwd(self, params, payload, split, key):
        return self._cloud_counts(params, payload, split, key)[:2]

    # -- two-pool cloud trunk: LLM blocks [split, split2)
    def _cloud_mid_fwd(self, params, payload, split, split2):
        cfg, plan = self.cfg, self.plan
        x = decode_activation(payload, cfg.dtype)
        positions = jnp.arange(x.shape[1])
        x = self._span(params, x, positions, split, split2)
        k = self._tail_slice()
        if k:
            x = x[:, -k:]       # semantic downlink: only what the tail reads
        return encode_activation(x, plan.codec2)

    # -- two-pool edge tail: LLM blocks [split2, Lv+L) + action
    def _tail_fwd(self, params, payload, split2, key):
        cfg = self.cfg
        x = decode_activation(payload, cfg.dtype)
        positions = jnp.arange(x.shape[1])
        x = self._span(params, x, positions, split2,
                       cfg.vit_layers + cfg.n_layers)
        return self._action_decode(params, x, key)

    def run(self, params, patches, tokens, split: int,
            key: Optional[jax.Array] = None,
            split2: Optional[int] = None, return_logits: bool = False,
            return_counts: bool = False):
        """One co-inference, dispatched as in ``LMSplitExecutor.run``.
        Single-pool plans return ``(action, uplink_payload)``; two-pool
        plans take the second cut ``split2`` and return ``(action, {"up":
        ..., "down": ...})`` with the action decoded on the edge tail.
        ``return_logits`` inserts the detok head's action-position logits
        (``None`` for other heads): ``(action, logits, payload)``.
        ``return_counts`` (single-pool plans) runs the programs that also
        give the trunk's dispatch counts on the device, appended as a
        dict of ``models/moe.COUNTS`` over both tiers."""
        if return_counts and self.plan.two_pool:
            raise NotImplementedError("dispatch counts of a two-pool plan")
        edge = self._edge_counted if return_counts else self._edge
        cloud = self._cloud_counted if return_counts else self._cloud
        with TraceAnnotation("roboecc/serve/edge"):
            split = jnp.int32(self.plan.clamp(split))
            payload = edge(params, patches, tokens, split)
            if return_counts:
                payload, c_edge = payload
        with TraceAnnotation("roboecc/serve/cloud"):
            key = key if key is not None else jax.random.PRNGKey(0)
            if not self.plan.two_pool:
                action, logits, *c_cloud = cloud(params, payload, split, key)
            else:
                split2 = jnp.int32(self.plan.clamp2(
                    split2 if split2 is not None else self.plan.pool2_end))
                down = self._cloud_mid(params, payload, split, split2)
                action, logits = self._tail(params, down, split2, key)
                payload = {"up": payload, "down": down}
        out = (action, logits, payload) if return_logits else (action, payload)
        if return_counts:
            both = MOE.add_counts(c_edge, c_cloud[0])
            out += (dict(zip(MOE.COUNTS, both)),)
        return out

    def run_streamed(self, params, patches, tokens, split: int,
                     n_chunks: int, key: Optional[jax.Array] = None,
                     split2: Optional[int] = None):
        """One co-inference with the uplink payload shipped in
        ``n_chunks`` token-axis chunk slices — the VLA sibling of
        ``LMSplitExecutor.run_streamed`` (actions bit-identical to
        ``run``; one trace per function across chunk counts)."""
        split_t = jnp.int32(self.plan.clamp(split))
        payload = self._edge(params, patches, tokens, split_t)
        chunks = chunk_payload(payload, n_chunks)
        merged = merge_chunks(chunks)
        key = key if key is not None else jax.random.PRNGKey(0)
        if not self.plan.two_pool:
            return self._cloud(params, merged, split_t, key)[0], chunks
        split2_t = jnp.int32(self.plan.clamp2(
            split2 if split2 is not None else self.plan.pool2_end))
        down = self._cloud_mid(params, merged, split_t, split2_t)
        action, _ = self._tail(params, down, split2_t, key)
        return action, {"up": chunks, "down": down}
