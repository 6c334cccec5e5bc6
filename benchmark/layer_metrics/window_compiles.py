"""Compilations counted by /v1/programs between window open and close. Expected 0."""


def read(ctx):
  return float(ctx["window_compiles"])
