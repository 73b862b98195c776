"""Layer ``device``: peak bytes in use on the fullest chip since the
process started, as the backend reports it
(``memory_stats()["peak_bytes_in_use"]``), in GB."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
