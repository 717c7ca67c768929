"""Programs compiled during set-up because the persistent cache lacked them
(``jax.monitoring`` cache-miss events up to the start of the window).  0 in
every run after a checkout's first."""


def read(record):
    return record["setup_cache_misses"]
