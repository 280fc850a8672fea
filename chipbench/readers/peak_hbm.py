"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip when the
window closed, in GB (1e9 bytes)."""


def read(metric, obs):
    peak = obs["memory_peak_bytes"]
    return peak / 1e9 if peak else None
