"""What moving every per-kind part into ``arch_<kind>.py`` (PR 26) must not
change, against values recorded from the parent commit ``bb2de62`` before
anything moved (``data/pins_bb2de62.json`` and ``.npz``; XLA's CPU backend,
jax 0.9.0): at the rehearsal widths every weight leaf from a fixed seed, to the
byte; the reference's log-probs on a fixed prompt, plain and under every
probe; the limits of ``correct``; and at the published configurations the
byte and operation counts the rooflines divide by, to the last digit.

Re-recorded by design in PR 39, and nothing else: ``published["moonlight-a3b-d14"]["decode_step_min_bytes"]`` (6.754 ->
6.463 GB at 16 rows, 2.692 -> 2.666 at 3; one row touches its six experts either way). The parent counted the experts a
step touches under uniform, independent routing; the configuration file states a topic router (``router_topics`` 64:
two rows of one topic choose the same six), and ``flops_bytes.experts_touched`` now counts under the router the file
states. The parent's three values stay beside them as ``decode_step_min_bytes_uniform_routing`` and are still met to
the last digit by the same file with ``router_topics`` 0; the operation counts did not move."""

import hashlib
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import arch  # noqa: E402
import common  # noqa: E402
import flops_bytes as fb  # noqa: E402
import jax  # noqa: E402
import layer_lib  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
PINS = json.loads((DATA / "pins_bb2de62.json").read_text())
KINDS = sorted(PINS["kinds"])


def _tiny(kind: str) -> dict:
  hf = common.load_config(PINS["kinds"][kind]["config"])
  assert hf["arch_kind"] == kind
  hf.update(arch.load(kind).REHEARSE_WIDTHS)
  hf["serving_window_tokens"] = 256
  return hf


@pytest.mark.parametrize("kind", KINDS)
def test_rehearsal_widths_limits_and_probes_are_the_parents(kind):
  pin, mod = PINS["kinds"][kind], arch.load(kind)
  assert mod.REHEARSE_WIDTHS == pin["rehearse_widths"]
  assert [mod.LIMITS[n] for n in arch.LIMIT_NAMES] == pin["limits"]
  assert list(mod.probes(_tiny(kind))) == pin["probes"]


@pytest.mark.parametrize("kind", KINDS)
def test_every_weight_leaf_is_the_parents(kind):
  params = weights.build_params(_tiny(kind), PINS["seed"])
  got = {}
  for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
    a = np.asarray(leaf)
    got[jax.tree_util.keystr(path)] = [str(a.dtype), list(a.shape), hashlib.sha256(a.tobytes()).hexdigest()]
  want = PINS["kinds"][kind]["leaves"]
  assert sorted(got) == sorted(want)
  assert {k: v for k, v in got.items() if v != want[k]} == {}


@pytest.mark.parametrize("kind", KINDS)
def test_the_references_logprobs_are_the_parents(kind):
  hf = _tiny(kind)
  params = weights.build_params(hf, PINS["seed"])
  tokens = np.random.default_rng([PINS["seed"], 11]).integers(3, hf["vocab_size"], size=40)
  pinned = np.load(DATA / "pins_bb2de62.npz")
  for name, kw in {"plain": {}, **arch.load(kind).probes(hf)}.items():
    got = np.asarray(reference.reference_logprobs(params, hf, tokens, 8, **kw), np.float32)
    np.testing.assert_allclose(got, pinned[f"{kind}.{name}"], rtol=0, atol=2e-6, err_msg=name)


@pytest.mark.parametrize("config", sorted(PINS["published"]))
def test_bytes_and_operations_at_the_published_sizes_are_the_parents(config):
  hf, pin = common.load_config(config), PINS["published"][config]
  kvq = layer_lib.kv_quant({"hf": hf})
  assert kvq == pin["kv_quant"]
  cases = ((16.0, 12800.0), (3.0, 1234.0), (1.0, 64.0))
  if "decode_step_min_bytes_uniform_routing" in pin:  # a file with a topic router (the docstring): the parent's count where it states none ...
    assert [fb.decode_step_min_bytes({**hf, "router_topics": 0}, r, t, kvq) for r, t in cases] == pin["decode_step_min_bytes_uniform_routing"]
    assert [fb.decode_step_min_bytes(hf, r, t, kvq) for r, t in cases] == pytest.approx(pin["decode_step_min_bytes"], rel=1e-12)  # ... and PR 39's as it states it (a sum of 65 rounded terms)
    assert all(new <= old for new, old in zip(pin["decode_step_min_bytes"], pin["decode_step_min_bytes_uniform_routing"]))
  else:
    assert [fb.decode_step_min_bytes(hf, r, t, kvq) for r, t in cases] == pin["decode_step_min_bytes"]
  assert [fb.decode_step_flops(hf, r) for r, _ in cases] == pin["decode_step_flops"]
  if "paged_attention_min_bytes" in pin:  # the parent's was the dense kind's formula, read in Mistral's cells only
    assert [fb.paged_attention_min_bytes(hf, r, t, kvq) for r, t in cases] == pin["paged_attention_min_bytes"]
