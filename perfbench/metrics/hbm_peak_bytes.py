"""memory: ``device.memory_stats()["peak_bytes_in_use"]`` after the
window."""


def read(run):
    return run.get("hbm_peak_bytes")
