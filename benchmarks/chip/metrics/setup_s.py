"""Set-up seconds: process start to the window's opening, compilation,
weight making and warm-up included (host clock)."""


def read(rec):
    return rec.setup_s
