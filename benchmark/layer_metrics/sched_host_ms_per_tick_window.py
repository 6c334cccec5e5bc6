"""Host milliseconds the scheduler works per tick (admit + plan + stage + settle), as ``sched_host_ms_per_tick`` but from the loop's own
clock: its phase seconds and ticks between the first and the last snapshot of the window up to the capture's opening (20 s and 100-250
ticks, not the ticks one capture holds whole), and none after it, when a capture has left the host slower (``clock_lib``) (.open, .closed)."""
import clock_lib


def read(ctx):
  return clock_lib.host_ms_per_tick(ctx)
