"""Set-up: process start to the opening of the measured window
(weights drawn on the device, engine built, the cell's programs warmed,
and for an open-loop mix the arrival ramp)."""


def read(rec):
    return rec["setup_s"]
