"""Share of the traced span's device time spent synthesising facet
planes from point components on the device (scope ``fwd.facet_synth``
of the facet-slab plan's fused step), averaged over the cell's chips
(layer: stage programs).

The synthesis is not a stage of the SwiFTly transform, so `counts`
gives it no least work and a roofline of it would read 0 whatever it
cost: its share of the busy time says what it takes from the stages
that are."""

SCOPES = ["fwd.facet_synth"]


def read(reading):
    busy = reading.busy_s()
    synth = reading.seconds(SCOPES) / max(len(reading.devices), 1)
    if busy <= 0 or synth <= 0:
        return None
    return 100.0 * synth / busy
