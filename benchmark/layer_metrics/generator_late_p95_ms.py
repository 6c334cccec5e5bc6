"""How late the open-loop generator sent against when each request was due."""


def read(ctx):
  return ctx.get("late_p95_ms")
