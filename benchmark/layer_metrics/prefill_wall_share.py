"""Of the scheduler loop's busy wall time over the window up to the capture (its own clock, snapshotted on the requests' timelines:
``clock_lib``), the share in which a prefill group was the oldest dispatch not read back — every resident row waits for it. Blind to a mixed
tick's prefill half: the slice rides a decode chunk and the whole tick is booked as ``mixed`` (``mistral-7b.decode-closed``: 0.08 here
beside 0.41 of ``mixed``, in the ``wall`` event), so only a server that prefills in groups of its own is read whole (.open, .closed)."""
import clock_lib


def read(ctx):
  return clock_lib.wall_share(ctx, "prefill")
