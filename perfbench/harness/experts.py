"""Device time of a dropless MoE trunk's routed experts in one traced
window.

The program runs each MoE layer's routing under the ``jax.named_scope``
``router`` and the routed experts' dispatch, grouped matmuls and combine
under ``experts``, both inside the tier's ``trunk`` scope.  The device
trace names an operation by its HLO instruction alone, so each tier is
compiled afresh (``probe.compiled_texts``) and its instructions mapped to
these two scopes by ``inside.scope_map``'s rules, with the innermost of
the two in place of the tier-level scopes.  A program without the
scopes maps nothing, and the readings below are ``None``.

How much routed work a call holds, the router decides: the rows sent to
held experts and the held experts given a row (only their weights are
read).  ``call_counts`` takes both from the configuration's reference
(its ``routing`` and ``dispatch_counts``) on each traced call's
observation at the call's split, after the window, so that the yardstick does not
rest on the program's own counters; a reference without ``routing``
gives ``None``.
"""
from __future__ import annotations

from typing import Dict, List, Optional
from unittest import mock

import jax.numpy as jnp
import numpy as np

from harness import inside

ROUTER, EXPERTS = "router", "experts"
_MAPS: Dict[int, Dict[str, Dict[str, str]]] = {}


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction -> ``router`` or ``experts`` for one compiled
    program."""
    with mock.patch.object(inside, "SCOPES", (ROUTER, EXPERTS)):
        return inside.scope_map(hlo_text)


def scope_maps(cell) -> Dict[str, Dict[str, str]]:
    """Each tier's map, from one fresh compile per cell."""
    if id(cell) not in _MAPS:
        import probe
        _MAPS[id(cell)] = {p: scope_map(t) for p, t
                           in probe.compiled_texts(cell).items()}
    return _MAPS[id(cell)]


def expert_seconds(trace, programs: Dict[str, str],
                   scopes: Dict[str, Dict[str, str]]) -> List[float]:
    """Per traced call: device seconds under ``experts``, both tiers
    summed (the union of each tier's operation intervals)."""
    ops = sorted(trace.ops(), key=lambda e: e[1])
    per_tier = [[inside.scope_seconds(c, EXPERTS) for c in inside.op_scopes(
        ops, trace.modules(fn), scopes.get(p, {}))]
        for p, fn in programs.items()]
    return [sum(t) for t in zip(*per_tier)]


def window_expert_s(w) -> Optional[List[float]]:
    """``expert_seconds`` of a ``run.Window``; ``None`` where no operation
    of the window is under ``experts``."""
    t = expert_seconds(w.trace, w.cell.programs(), scope_maps(w.cell))
    return t if any(t) else None


def call_counts(cell, calls: List[Dict]) -> Optional[List[Dict[str, int]]]:
    """Each completed call's routed work (``routed``, ``reached``), from
    the reference's routing of the call's observations at its split.  The
    calls' observations go through the reference in blocks of its
    ``REQUEST_BLOCK``, padded with the last, so it compiles the block
    shape the check uses."""
    ref, m = cell.ref, cell.m
    if not hasattr(ref, "routing"):
        return None
    done = [c for c in calls if c["split"] is not None]   # not failed
    by_split: Dict[int, List[int]] = {}
    for c in done:
        rings = by_split.setdefault(c["split"], [])
        if c["ring"] not in rings:
            rings.append(c["ring"])
    n = ref.REQUEST_BLOCK
    counts = {}
    for split, rings in sorted(by_split.items()):
        patches = np.concatenate([cell.patches[r] for r in rings])
        tokens = np.concatenate([cell.tokens[r] for r in rings])
        pad = -len(patches) % n
        patches = np.concatenate([patches, patches[-1:].repeat(pad, 0)])
        tokens = np.concatenate([tokens, tokens[-1:].repeat(pad, 0)])
        chosen = ref.routing(m, cell.params, jnp.asarray(patches),
                             jnp.asarray(tokens), split)
        b = len(cell.patches[rings[0]])                  # robots per call
        for j, r in enumerate(rings):
            counts[r, split] = ref.dispatch_counts(
                m, chosen[j * b:(j + 1) * b])
    return [counts[c["ring"], c["split"]] for c in done]
