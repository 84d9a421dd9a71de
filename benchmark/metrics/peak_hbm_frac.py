"""Peak device memory after the window, as a share of the chip's limit,
on the fullest chip (layer: device). Read from the runtime's
``memory_stats()``."""


def read(reading):
    if not reading.mem_limit:
        return None
    return 100.0 * reading.mem_peak / reading.mem_limit
