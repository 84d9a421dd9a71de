"""Roofline share of the forward facet pass (layer: stage programs).

Device time of the operations under the forward facet-pass scopes
against the least time of `counts.fwd_facet_pass` over the columns the
span ran."""

SCOPES = ["fwd.sampled_facet_pass", "fwd.facet_pass", "fwd.facet_synth"]


def read(reading):
    return reading.roofline(SCOPES, ["fwd_facet_pass"])
