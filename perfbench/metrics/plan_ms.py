"""Control plane: mean host time per step of the RoboECC controller's
``tick`` (LSTM bandwidth forecast plus split adjustment), from the
benchmark's own span around the call.  Nothing to read where the mix
runs no per-step controller."""


def read(w):
    ticks = [c["tick_s"] for c in w.calls if c.get("tick_s")]
    if not ticks:
        return None
    return 1e3 * sum(ticks) / len(ticks)
