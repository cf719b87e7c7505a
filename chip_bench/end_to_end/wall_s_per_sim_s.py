"""Wall seconds of the window's whole chunks per simulated second."""


def read(rec):
    return rec.window_s / (rec.steps * rec.dt_ms / 1000.0)
