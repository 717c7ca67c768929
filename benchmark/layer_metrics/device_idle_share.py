"""1 - (union of device-op intervals / traced window), %, chip 0."""


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s_device0"] / tr["window_s"])
