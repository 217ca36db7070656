"""Share of the traced window in which no operation ran on the device
(1 - busy / window, busy = union of the device's op intervals), in %."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["truncated"] or not tr["window_s"] or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
