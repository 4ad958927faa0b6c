"""Seconds of JAX backend compiles during set-up (0 once every program
comes from the persistent cache)."""


def read(rd):
    return rd["setup_compile"][0]
