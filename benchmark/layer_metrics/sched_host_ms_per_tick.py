"""Host milliseconds the scheduler works per tick (admit + plan + stage + settle; one tick is one program dispatch),
from the ``xot.sched.*`` spans of the ticks the traced interval holds whole; the spans share their boundaries with the
program's ``sched_phase_seconds_total``. ``readback`` is mostly a wait for the device: logged beside it, not summed (.open, .closed)."""
import span_lib


def read(ctx):
  red = span_lib.capture(ctx)
  phases = span_lib.phase_ms_per_tick(red) if red else None
  return sum(phases.get(p, 0.0) for p in span_lib.WORKING_PHASES) if phases else None
