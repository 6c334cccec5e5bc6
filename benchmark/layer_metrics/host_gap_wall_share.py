"""Of the scheduler loop's busy wall time over the window up to the capture (``clock_lib``), the share in which the loop thread had nothing
dispatched and work pending: last readback to the loop's next hand-over to the executor. It is NOT the chip's idle share and reads a third
to a half of a capture's ``idle_share`` (PR 41): the executor's half of ``stage`` (transfers, the jitted call), during which the chip still
waits, is booked to the dispatch's own kind (.open, .closed)."""
import clock_lib


def read(ctx):
  return clock_lib.wall_share(ctx, "host")
