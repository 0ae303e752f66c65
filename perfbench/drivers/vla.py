"""Drives one split-VLA cell through the program's served path.

Set-up builds the system under test the way ``repro.launch.serve``
serves it: the RoboECC controller (Alg. 1 split and parameter-sharing
pool, optionally the LSTM bandwidth predictor trained on the mix's
seeded trace), the split executor with the int8 wire codec on the cut,
and the benchmark's own seeded bfloat16 weights.  Each step hands one
call's observations to ``serve_request`` as host arrays and ends when
the action is on the host.  After the window the served outputs of a
seeded sample of requests are compared with the float32 reference in
``references/vla.py``.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness import traffic as gen
from harness.weights import make_params, seed_key, tree_shapes


def _rel_max(a: np.ndarray, b: np.ndarray) -> float:
    """Largest absolute difference over the reference's largest value."""
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        return float("inf")
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# numbers pooled over the sample: the norm of all differences over the
# norm of all reference values (the others are the worst request's)
POOLED = ("cut_rms", "logit_rms", "action_rms")


def _sq(a: np.ndarray, b: np.ndarray) -> List[tuple]:
    """Per request: squared norms of the difference and of the
    reference."""
    d = np.asarray(a, np.float64) - b
    return list(zip(np.sum(d.reshape(len(d), -1) ** 2, axis=1),
                    np.sum(np.asarray(b, np.float64).reshape(len(b), -1) ** 2,
                           axis=1)))


def _reduce(name: str, vals: List) -> float:
    """The sample's number from its per-request values."""
    if name in POOLED:
        d, r = np.sum(np.asarray(vals, np.float64), axis=0)
        return float(np.sqrt(d / max(r, 1e-60))) if np.isfinite(d) \
            else float("inf")
    return float(np.max(vals))


def _head(out: Dict, n: int) -> Dict[str, np.ndarray]:
    """The first ``n`` rows of each reference output, on the host."""
    return {k: np.asarray(v, np.float32)[:n] for k, v in out.items()}


class Reservoir:
    """A uniform sample of ``k`` of the items offered (Algorithm R),
    drawn from its own seeded generator."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.items: List = []
        self.seen = 0
        self.rng = np.random.default_rng([int(seed) % (1 << 64), 2])

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            self.items[j] = item


class Cell:
    """One configuration under one traffic mix, built from ``seed``."""

    def __init__(self, config: Dict, traffic: Dict, seed: int, log,
                 load: Callable):
        """``load(kind, name)`` returns the benchmark's module
        ``<kind>/<name>.py`` (the configuration's reference and costs)."""
        from repro.configs import get_config
        from repro.core import NetworkSim
        from repro.core.predictor import PredictorConfig
        from repro.launch import serve
        from repro.models import build
        from repro.models.sharding import is_spec

        self.serve = serve
        self.m = m = dict(config["model"])
        self.log = log
        self.ref = load("references", config["reference"])
        self.costs = load("costs", config["reference"])
        self.text = int(traffic["text_tokens"])
        self.batch = int(traffic["batch"])
        self.codec = traffic["codec"]
        self.phases: Dict[str, float] = {}

        t = time.perf_counter()
        self.cfg = get_config(config["program_arch"]).replace(**m)
        want = tree_shapes(self.ref.param_shapes(m))
        flat = jax.tree_util.tree_flatten_with_path(
            build(self.cfg).param_specs, is_leaf=is_spec)[0]
        have = {jax.tree_util.keystr(p): tuple(s.shape) for p, s in flat}
        if have != want:
            diff = sorted(k for k in set(have) | set(want)
                          if have.get(k) != want.get(k))
            raise RuntimeError(f"the program's parameter layout differs from "
                               f"the reference's at {diff[:8]}")
        key = seed_key(seed)
        self.params = make_params(self.ref.param_shapes(m),
                                  jax.random.fold_in(key, 0))
        jax.block_until_ready(self.params)
        self.phases["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        ctl_doc = traffic["controller"]
        self.adjust = bool(ctl_doc["adjust"])
        self.ctl, _ = serve.build_controller(self.cfg, self.codec, 0,
                                             predictor_epochs=0,
                                             seq=self.text)
        trace = gen.bandwidth_trace(ctl_doc["trace_ticks"],
                                    traffic["bandwidth_trace"], seed)
        n_fit = int(ctl_doc["train_ticks"])
        window = PredictorConfig().window
        if self.adjust:
            self.ctl.fit_predictor(
                trace[:n_fit],
                PredictorConfig(epochs=int(ctl_doc["predictor_epochs"])),
                seed=int(seed) % (1 << 31))
            window = self.ctl.predictor.cfg.window
        self.net = NetworkSim(trace[n_fit:])
        self.net.step(window)
        self.ex = serve.build_executor(self.cfg, self.ctl, self.codec)
        self.split0 = serve.executor_index(self.cfg, self.ctl.graph,
                                           self.ctl.split)
        self.phases["controller_s"] = time.perf_counter() - t

        t = time.perf_counter()
        self.patches, self.tokens = gen.observation_ring(m, traffic, seed)
        self.keys = [jax.random.fold_in(key, 1 + i)
                     for i in range(len(self.patches))]
        jax.block_until_ready(self.keys)
        self.phases["inputs_s"] = time.perf_counter() - t

        # the precision ("int8", "fp8") of a reference put in the
        # program's place when the outputs are compared: the control
        self.control = None
        self.sample = Reservoir(int(traffic["check_requests"]), seed)
        self.kept: Dict[int, Dict] = {}
        self.calls: List[Dict] = []

        t = time.perf_counter()
        for i in range(int(traffic["warmup_steps"])):
            self.step(-1 - i)
        self.phases["warmup_s"] = time.perf_counter() - t
        self.calls.clear()
        log(f"executor pool [{self.ex.plan.pool_start},"
            f"{self.ex.plan.pool_end}), Alg. 1 split {self.split0}, "
            f"adjust {self.adjust}, batch {self.batch}")

    # --------------------------------------------------------------- steps
    def programs(self) -> Dict[str, str]:
        """Program names the device trace gives the two tiers."""
        return {"edge": "_edge_fwd", "cloud": "_cloud_fwd"}

    def step(self, i: int) -> Dict:
        """One call: ``batch`` robots' observations in, actions on the
        host.  Negative ``i`` are warm-up steps (not sampled)."""
        TA = jax.profiler.TraceAnnotation
        c = i % len(self.patches)
        rec = {"i": i, "ring": c, "robots": self.batch, "failed": 0,
               "tick_s": 0.0}
        if self.adjust and self.net.t + 1 >= len(self.net.trace):
            raise RuntimeError(
                f"step {i}: the bandwidth trace's {len(self.net.trace)} "
                f"ticks are used up; lengthen the mix's trace_ticks")
        t0 = time.perf_counter()
        try:
            if self.adjust:
                with TA("tick"):
                    tick = self.ctl.tick(self.net)
                    split = self.serve.executor_index(self.cfg,
                                                      self.ctl.graph,
                                                      tick.split)
                rec["tick_s"] = time.perf_counter() - t0
            else:
                split = self.split0
            with TA("upload"):
                inputs = (jax.device_put(self.patches[c]),
                          jax.device_put(self.tokens[c]))
            with TA("serve"):
                served = self.serve.serve_request(self.ex, self.params,
                                                  inputs, split,
                                                  self.keys[c])
            with TA("fetch"):
                action = np.asarray(served.out)
            ok = np.isfinite(action.reshape(self.batch, -1)).all(axis=1)
            rec["failed"] = int((~ok).sum())
        except Exception as e:                   # a failed step, counted
            self.log(f"step {i} failed: {type(e).__name__}: {e}")
            rec.update(t0=t0, t1=time.perf_counter(), failed=self.batch,
                       split=None)
            self.calls.append(rec)
            return rec
        rec.update(t0=t0, t1=time.perf_counter(),
                   split=self.ex.plan.clamp(split))
        self.calls.append(rec)
        if i >= 0:
            self._keep(i, rec, served, action, ok)
        return rec

    def _keep(self, i, rec, served, action, ok) -> None:
        out = {"rec": rec, "action": action, "logits": served.logits,
               "payload": served.payload, "refs": 0}
        self.kept[i] = out
        for r in range(self.batch):
            if not ok[r]:
                continue
            before = list(self.sample.items)
            self.sample.offer((i, r))
            if self.sample.items != before:
                out["refs"] += 1
                for j, _ in set(before) - set(self.sample.items):
                    self._drop(j)
        if out["refs"] == 0:
            del self.kept[i]

    def _drop(self, i: int) -> None:
        out = self.kept[i]
        out["refs"] -= 1
        if out["refs"] == 0:
            del self.kept[i]

    def call_costs(self, rec: Dict) -> Dict[str, tuple]:
        """``{program: (flops, bytes)}`` of one recorded call."""
        m, s, b = self.m, rec["split"], rec["robots"]
        return {"edge": self.costs.edge_cost(m, s, b, self.text),
                "cloud": self.costs.cloud_cost(m, s, b, self.text)}

    def step_flops(self) -> float:
        return self.costs.step_flops(self.m, self.text)

    def release(self) -> None:
        """Drop the program's state beyond the sample and the weights."""
        self.ex = None

    # --------------------------------------------------------------- check
    def check(self, limits: Dict[str, float], controls=(),
              ref_batch: int = 16) -> Dict[str, Dict]:
        """Compare the sampled requests' served outputs with the float32
        reference.  Each number is the worst request's, or pooled over the
        sample (``POOLED``); those named in ``limits`` are compared.  With
        ``self.control`` set, the reference computed in that precision
        takes the served outputs' place.  ``controls`` ("int8", "fp8")
        also read every number, compared or not, for the reference in
        that precision in the program's place."""
        items = sorted(self.sample.items)
        if not items:
            raise RuntimeError("no completed request to compare")
        m, R = self.m, self.ref
        detok = m["vla_action_head"] == "detok"
        nums: Dict[str, List] = {}
        ctl: Dict[str, Dict[str, List]] = {q: {} for q in controls}
        by_split: Dict[int, List] = {}
        for i, r in items:
            by_split.setdefault(self.kept[i]["rec"]["split"], []).append(
                (i, r))
        for split, group in sorted(by_split.items()):
            for lo in range(0, len(group), ref_batch):
                part = group[lo:lo + ref_batch]
                # every block has ref_batch rows (the last request
                # repeated), so the reference compiles one shape only
                pad = part + part[-1:] * (ref_batch - len(part))
                patches, tokens, noise = self._inputs(pad)

                def reference(quant=None):
                    return _head(R.forward(m, self.params, patches, tokens,
                                           split, noise=noise, quant=quant),
                                 len(part))
                ref = reference()
                got = (self._as_served(reference(self.control), detok)
                       if self.control else self._served(part))
                self._numbers(nums, got, ref, detok)
                for q in controls:
                    self._numbers(ctl[q], self._as_served(reference(q),
                                                          detok), ref, detok)
        out = {}
        for name, vals in nums.items():
            if name not in limits and not controls:
                continue
            entry = {"value": _reduce(name, vals)}
            if name in limits:
                entry["limit"] = float(limits[name])
            for q in controls:
                entry[f"control_{q}"] = _reduce(name, ctl[q][name])
            out[name] = entry
        out["compared"] = len(items)
        return out

    def _inputs(self, part):
        m = self.m
        patches = np.stack([self.patches[self.kept[i]["rec"]["ring"]][r]
                            for i, r in part])
        tokens = np.stack([self.tokens[self.kept[i]["rec"]["ring"]][r]
                           for i, r in part])
        noise = None
        if m["vla_action_head"] == "dit":
            noise = jnp.stack([
                self.ref.dit_noise(m, self.keys[self.kept[i]["rec"]["ring"]],
                                   self.batch)[r] for i, r in part])
        return jnp.asarray(patches), jnp.asarray(tokens), noise

    def _served(self, part) -> Dict[str, np.ndarray]:
        got = {"cut": [], "logits": [], "action": []}
        for i, r in part:
            k = self.kept[i]
            p = k["payload"]
            q = np.asarray(p["q"][r], np.float32)
            s = np.asarray(p["s"][r], np.float32)
            blk = q.shape[-1] // s.shape[-1]
            got["cut"].append((q.reshape(*s.shape, blk) * s[..., None])
                              .reshape(q.shape))
            got["action"].append(np.asarray(k["action"][r], np.float32))
            if k["logits"] is not None:
                got["logits"].append(np.asarray(
                    k["logits"][r, :, :self.m["vocab_size"]], np.float32))
        return {k: np.stack(v) for k, v in got.items() if v}

    def _as_served(self, low, detok) -> Dict[str, np.ndarray]:
        """The control's outputs in the served form."""
        got = {"cut": np.asarray(low["cut"], np.float32)}
        if detok:
            lg = np.asarray(low["logits"], np.float32)
            got["logits"] = lg
            tok = lg.argmax(-1)
            got["action"] = ((tok % 256) / 127.5 - 1.0)[:, None, :]
        else:
            got["action"] = np.asarray(low["action"], np.float32)
        return got

    def _numbers(self, nums, got, ref, detok) -> None:
        """Per-request numbers, appended under their names."""
        def put(name, v):
            nums.setdefault(name, []).extend(v)

        cut = np.asarray(ref["cut"], np.float32)
        put("cut_err", [_rel_max(got["cut"][j], cut[j])
                        for j in range(len(cut))])
        put("cut_rms", _sq(got["cut"], cut))
        if detok:
            r = np.asarray(ref["logits"], np.float32)
            tok = got["logits"].argmax(-1)                   # served tokens
            best = r.max(-1)
            at = np.take_along_axis(r, tok[..., None], -1)[..., 0]
            gap = (best - at) / r.std(-1)
            put("token_gap", gap.max(-1).tolist())
            put("logit_rms", _sq(got["logits"], r))
            # the action is the served token's bin: (token % 256) / 127.5 - 1
            bins = np.rint((got["action"][:, 0] + 1.0) * 127.5)
            put("action_token", np.sum(bins != tok % 256, axis=1).tolist())
        else:
            a = np.asarray(ref["action"], np.float32)
            put("action_err", [_rel_max(got["action"][j], a[j])
                               for j in range(len(a))])
            put("action_rms", _sq(got["action"], a))
