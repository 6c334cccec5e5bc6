"""Build a tiny diffusers-format stable-diffusion checkpoint on disk.

Offline stand-in for stabilityai/stable-diffusion-2-1-base (no egress in
this environment): same on-disk layout (model_index.json, text_encoder/,
unet/, vae/, scheduler/), toy widths. Used by the verify drill and by
anyone who wants to exercise /v1/image/generations without a download.

Usage: python scripts/make_tiny_diffusion.py /tmp/tiny_sd
"""

from __future__ import annotations

import sys
from pathlib import Path

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # a toy checkpoint needs no chip — and must not take one from a daemon

import jax  # noqa: E402


def main() -> None:
  sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
  from xotorch_support_jetson_tpu.models.diffusion import tiny_diffusion_config
  from xotorch_support_jetson_tpu.models.diffusion_loader import (
    export_diffusers_checkpoint,
    init_diffusion_params,
  )

  out = Path(sys.argv[1] if len(sys.argv) > 1 else "/tmp/tiny_sd")
  cfg = tiny_diffusion_config()
  params = init_diffusion_params(jax.random.PRNGKey(0), cfg)
  export_diffusers_checkpoint(out, cfg, params)
  print(f"tiny diffusers checkpoint at {out}")


if __name__ == "__main__":
  main()
