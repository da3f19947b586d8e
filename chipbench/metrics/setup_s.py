"""Set-up time, s: process start to the window's opening (parameters,
engine, every tick program the mix reaches, the window's requests)."""


def read(r):
    return r.setup_s
