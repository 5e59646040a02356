"""Share (%) of the rows kernel's roofline in a closed batch window:
the least time the chip needs for the window's batched calls (bytes
over HBM bandwidth or operations over peak, whichever is larger;
``bench/roofline.py``) over the traced device time of every
``rows_*`` kernel event."""

from bench import roofline


def read(r):
    if r.get("loop") != "closed" or not r.get("kernel_s"):
        return None
    got = roofline.share(r["work"], r["calls"], r["kernel_s"], r["device_kind"])
    return None if got is None else got[0]
