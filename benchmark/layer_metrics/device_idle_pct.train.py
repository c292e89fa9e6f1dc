"""Device: share of the traced window in which no op ran on the device,
the window clipped to whole steps (first to last start of the
train-step program), so that start_trace and stop_trace fall outside."""

LAYER = "Device"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(context):
    trace = context["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
