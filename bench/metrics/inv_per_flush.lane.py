"""Invocations submitted per flush of the device lane's channel
(``Channel.stats['flushes']``) in the traced window."""


def read(r):
    f = r.counts.get("flushes", 0)
    return r.counts.get("submitted", 0) / f if f else None
