"""Share of the traced window in which no operation ran on the device, in
% (1 - union of device-op intervals / window).  Device layer, backlog
cells; moves samples_per_s."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
