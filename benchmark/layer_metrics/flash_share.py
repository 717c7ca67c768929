"""Device time of the flash (Mosaic) kernels, forward and backward, over
device busy time, %.  The kernels are the trace's ``tpu_custom_call`` ops;
flash attention is the only Pallas kernel on the training path."""


def read(record):
    tr = record["trace"]
    if tr is None or not tr["mosaic_invocations"]:
        return None
    return 100.0 * tr["mosaic_s"] / tr["busy_s_device0"]
