"""Median host wall of one serving tick (predict, apply, retire), from the
benchmark's ``tick`` spans."""


def read(rd):
    return rd["tick_s"]
