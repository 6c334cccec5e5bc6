"""Of the programs the scheduler loop handed to the device over the window up to the capture, the share it enqueued BEHIND one it
had not read back yet (the clock's counts ``dispatch_behind`` / ``dispatch_empty``, which move with
``sched_dispatches_total{queue}``: ``half_lib.count_ratio``) - the hit share of the lookahead pipeline: a dispatch onto an
empty queue is a stretch in which the chip waits for the host (.open, .closed). None for a program whose snapshots carry no counts."""
import half_lib


def read(ctx):
  return half_lib.count_ratio(ctx, ("dispatch_behind",), ("dispatch_behind", "dispatch_empty"))
