"""Device: the whole step's share of the chip's bf16 peak — model FLOPs
per robot step times the robot steps completed in the traced window,
over the window's seconds times the peak rate."""


def read(w):
    return 100.0 * w.step_flops * w.robot_steps / (w.window_s
                                                   * w.peak_flops)
