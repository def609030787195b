"""Peak bytes in use on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GB (1e9 bytes)."""


def read(run):
    return run["memory_peak_bytes"] / 1e9 if run.get("memory_peak_bytes") else None
