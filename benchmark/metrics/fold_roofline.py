"""Roofline share of the backward's fold into the facets (layer: stage
programs): the least time of the fold's work (`counts.bwd_fold`) over
the device time under the fold scopes, whichever fold body ran."""

SCOPES = ["bwd.sampled_fold", "bwd.fft_fold", "bwd.ct_fold"]


def read(reading):
    return reading.roofline(SCOPES, ["bwd_fold"])
