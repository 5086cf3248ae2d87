"""idle_share.batch: percent of the traced call in which the device ran
no operation: 100 * (1 - union of operation intervals / window)."""


def read(run):
    if run.device is None or run.device.window_s <= 0:
        return None
    return 100.0 * run.device.idle_share
