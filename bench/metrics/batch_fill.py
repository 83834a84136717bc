"""scheduler layer (``serving/batching.py``): real scenes over bucket
batch slots, summed over the window's forwards (``ServeStats`` items and
padded items), in %."""


def read(m):
    slots = m.stats["items"] + m.stats["padded_items"]
    return 100.0 * m.stats["items"] / slots if slots else None
