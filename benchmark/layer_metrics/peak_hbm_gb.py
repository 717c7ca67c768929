"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window, GB (10^9).  Each run is its own process, so the peak is the cell's."""
from benchmark.lib import system


def read(record):
    if record["rehearse"]:
        return None
    return system.memory_peak_bytes(record["cell"]["chips"]) / 1e9
