"""Peak device memory in use (``memory_stats()["peak_bytes_in_use"]``
after the window, fullest chip), in GB."""


def read(run):
    return run["device"]["peak_bytes"] / 1e9
