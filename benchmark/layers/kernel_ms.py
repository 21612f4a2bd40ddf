"""Layer: scan kernels. Device time per statement: the union of the device's
op intervals between a statement's send and its answer. Device trace."""

from benchlib.layerlib import device_ms, mean_of_family_means


def read(run):
    return mean_of_family_means(run, lambda r: device_ms(run, r))
