"""held_hbm_gb.*: largest memory_stats()["peak_bytes_in_use"] over the
cell's devices after the window: buffers held, not a program's
temporaries."""


def read(record, metric):
    b = record.get("memory_peak_bytes")
    return b / 1e9 if b else None
