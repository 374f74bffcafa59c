"""Peak bytes in use on the fullest chip, `memory_stats()`, in GB."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
