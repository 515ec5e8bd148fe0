"""Closed loop: ``clients`` callers, each sending its next request as soon
as the previous one has finished, with no pause.  A slow server receives
less load; there is no arrival schedule."""

CLOSED = True


def gaps(spec, u):
    raise TypeError("a closed loop has no arrival schedule")
