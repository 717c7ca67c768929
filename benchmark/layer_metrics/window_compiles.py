"""Backend compile events between the start and the end of the measured
window.  Must read 0: anything else compiled inside the window."""


def read(record):
    return record["window_compiles"]
