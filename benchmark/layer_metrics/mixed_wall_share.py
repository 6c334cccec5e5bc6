"""Of the scheduler loop's busy wall time over the window up to the capture (its own clock: ``clock_lib``), the share in which a
MIXED tick was the oldest dispatch not read back - the stretches in which the resident rows' steps carry someone else's prompt,
which ``prefill_wall_share`` is blind to (.open, .closed). The clock has booked the kind since PR 41; None for a program without it."""
import clock_lib


def read(ctx):
  return clock_lib.wall_share(ctx, "mixed")
