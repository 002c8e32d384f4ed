"""Set-up time: process start to the opening of the measured window
(imports, weights, engine, warm-up, and the cell's own set-up traffic)."""


def read(rec):
    return rec["setup_s"]
