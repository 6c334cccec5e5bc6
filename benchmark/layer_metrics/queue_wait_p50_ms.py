"""Milliseconds a request waits in the admission queue: the admission layer's ``queued`` stage (the one with ``queue_depth``; the node
writes a ``queued`` of its own earlier) to the scheduler's next ``admitted``, per request of the window, median (.open, .closed)."""
import layer_lib


def read(ctx):
  waits = []
  for r in ctx["recs"]:
    events = ((ctx.get("timelines") or {}).get(r.rid) or {}).get("events", ())
    queued = next((ev["at_ms"] for ev in events if ev["stage"] == "queued" and "queue_depth" in (ev.get("attributes") or {})), None)
    admitted = next((ev["at_ms"] for ev in events if ev["stage"] == "admitted" and queued is not None and ev["at_ms"] >= queued), None)
    if admitted is not None:
      waits.append(admitted - queued)
  return layer_lib.pct(waits, 50)
