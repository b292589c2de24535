"""patch_ms: mean per request of the summed ``repro.exec.patch`` spans (per
patched leaf, the diff rows' upload and the ``snapshot_patch`` dispatch) of
the window's cold requests, in ms (program span; host side, the device
time is ``patch_roofline``'s)."""

from bench.spans import mean_ms


def read(view):
    return mean_ms(view, lambda t: t.total("repro.exec.patch"))
