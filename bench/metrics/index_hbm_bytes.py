"""Bytes of the served device index arrays (every ``DeviceSegment``
leaf the entry serves from), summed over all devices."""


def read(run):
    return run.index_hbm_bytes
