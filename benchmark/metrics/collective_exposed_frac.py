"""Share of the traced span in which a collective (the forward column
pass's psum over the facet mesh) ran on a chip with nothing else
running there, averaged over the chips (layer: collective)."""


def read(reading):
    if not reading.collectives() or reading.span_s <= 0:
        return None
    return 100.0 * reading.exposed_collective_s() / reading.span_s
