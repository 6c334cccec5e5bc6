"""Of the KV pages the decode dispatches' rows held over the window up to the capture, in every layer that owns pages, the share their
layers' windows let the attention read (the clock's counts ``kv_pages_read`` / ``kv_pages_resident``, summed a dispatch from the
rows' lengths beside ``kv_pages_*_total``: ``half_lib.count_ratio``) - what a window returns in bandwidth, and what a pool
whose window layers held a window only would return in bytes (.closed). None for a program whose snapshots carry no counts."""
import half_lib


def read(ctx):
  return half_lib.count_ratio(ctx, ("kv_pages_read",), ("kv_pages_resident",))
