"""Share of the traced span in which no operation ran on the device,
averaged over the cell's chips (layer: device)."""


def read(reading):
    if not reading.devices or reading.span_s <= 0:
        return None
    return 100.0 * (1.0 - reading.busy_s() / reading.span_s)
