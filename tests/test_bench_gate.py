"""bench.py integrity guards (VERDICT r2 weak #1).

The round-2 driver artifact recorded a headline of 79,922.77 tok/s — a
timing artifact ~360x the HBM roofline — while the same run's serving path
measured 216.04. These tests pin the two guards that
keep that class of error out of the judged record: the headline sanity gate
and the plausibility filter used for the ``vs_baseline`` denominator.
"""

import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import gate_disagg, gate_failover, gate_headline, gate_kv_tier, gate_lookahead, gate_lora, gate_overload, gate_slo, gate_spec_batch, plausible_value

# The actual poisoned round-2 record (the driver's round-2 "parsed" payload;
# the record file itself is gone with the rest of the pre-PR-1 chip records).
R02 = {
  "metric": "decode_tokens_per_sec_llama1b_bf16_1chip",
  "value": 79922.77,
  "unit": "tokens/s",
  "serving_chunked_tok_s": 216.04,
}
# The honest round-1 record.
R01 = {
  "metric": "decode_tokens_per_sec_llama1b_bf16_1chip",
  "value": 220.69,
  "unit": "tokens/s",
  "serving_chunked_tok_s": 221.35,
}


def test_gate_fires_on_fake_fast_headline():
  value, tripped = gate_headline(79922.77, 216.04)
  assert tripped
  assert value == 216.04


def test_gate_passes_honest_headline():
  value, tripped = gate_headline(220.69, 221.35)
  assert not tripped
  assert value == 220.69
  # Mild skew (decode slightly faster than chunked serving) is real, not an
  # artifact: the serving path adds scheduling overhead.
  value, tripped = gate_headline(300.0, 220.0)
  assert not tripped and value == 300.0


def test_gate_without_serving_reference_is_identity():
  value, tripped = gate_headline(500.0, None)
  assert not tripped and value == 500.0


def test_plausible_value_rejects_poisoned_r02_record():
  assert plausible_value(R02) == 216.04


def test_plausible_value_keeps_honest_record():
  assert plausible_value(R01) == 220.69


def test_plausible_value_handles_missing_fields():
  assert plausible_value({}) is None
  assert plausible_value({"value": 100.0}) == 100.0


def test_lookahead_gate_keeps_plausible_ratios():
  """batch48_lookahead_vs_sync rides the same drift-gate pattern: overlap
  can only hide the per-chunk host window, so honest ratios sit near 1."""
  assert gate_lookahead(1.08) == 1.08
  assert gate_lookahead(0.97) == 0.97
  assert gate_lookahead(2.9) == 2.9


def test_lookahead_gate_drops_artifacts():
  # A 360x-style block_until_ready artifact on one side of the A/B cannot
  # enter the tracked record as a "scheduling win" (or loss).
  assert gate_lookahead(12.4) is None
  assert gate_lookahead(0.05) is None
  assert gate_lookahead(None) is None


def test_overload_gate_keeps_plausible_shed_rates():
  """The QoS overload round's shed rate is a fraction of offered load: a
  healthy 2x-overload run sheds some batch work, never (nearly) all of it."""
  assert gate_overload(0.0) == 0.0
  assert gate_overload(0.25) == 0.25
  assert gate_overload(0.9) == 0.9


def test_slo_gate_keeps_fractions_and_drops_artifacts():
  """ISSUE 9: attainment and goodput ratio are counter-delta fractions —
  [0, 1] exactly (1.0 is a legitimately perfect round and must survive the
  gate); outside means the delta went negative across a registry reset."""
  assert gate_slo(0.0) == 0.0
  assert gate_slo(0.97) == 0.97
  assert gate_slo(1.0) == 1.0
  assert gate_slo(1.2) is None
  assert gate_slo(-0.1) is None
  assert gate_slo(None) is None


def test_failover_gate_keeps_plausible_recoveries():
  """ISSUE 8: kill-to-next-token recovery on the localhost drill is the
  replay delay plus one re-prefill — tens of ms to tens of seconds."""
  assert gate_failover(250.0) == 250.0
  assert gate_failover(3200.5) == 3200.5
  assert gate_failover(1.0) == 1.0


def test_failover_gate_drops_artifacts():
  """Sub-millisecond recovery means a token raced the kill; beyond 120 s the
  stream wedged into an outer timeout — both dropped, not recorded."""
  assert gate_failover(0.2) is None
  assert gate_failover(500000.0) is None
  assert gate_failover(None) is None


def test_kv_tier_gate_keeps_plausible_values():
  """ISSUE 6: spill/restore bandwidths inside [0.01, 1000] GB/s pass
  through unchanged; the resume A/B ratio rides the same gate with its own
  bounds."""
  assert gate_kv_tier(1.5) == 1.5
  assert gate_kv_tier(80.0) == 80.0
  assert gate_kv_tier(0.01) == 0.01
  assert gate_kv_tier(3.7, lo=1.0 / 3.0, hi=100.0) == 3.7


def test_kv_tier_gate_drops_artifacts():
  """A PCIe copy cannot run at terabytes/s (a timer that stopped early) or
  at ~zero (a stall) — both are timing artifacts, dropped rather than
  recorded."""
  assert gate_kv_tier(2000.0) is None
  assert gate_kv_tier(0.0) is None
  assert gate_kv_tier(-1.0) is None
  assert gate_kv_tier(None) is None
  assert gate_kv_tier(500.0, lo=1.0 / 3.0, hi=100.0) is None


def test_overload_gate_drops_artifacts():
  # A wedged scheduler shedding the world (or a counter going negative
  # across a registry reset) must not enter the tracked record.
  assert gate_overload(1.0) is None
  assert gate_overload(0.99) is None
  assert gate_overload(-0.1) is None
  assert gate_overload(None) is None


def test_spec_batch_gate_keeps_plausible_ratios():
  """ISSUE 7: the batched-spec/plain A/B ratio lives in ~[0.5, gamma+1] —
  parity-ish at the adaptive floor, up to ~5x at full acceptance/gamma 4."""
  assert gate_spec_batch(1.0) == 1.0
  assert gate_spec_batch(0.6) == 0.6
  assert gate_spec_batch(3.4) == 3.4
  assert gate_spec_batch(7.9) == 7.9


def test_spec_batch_gate_drops_artifacts():
  # An early block_until_ready return on one side of the A/B must not enter
  # the record as a 50x "speculation win" (or a near-zero collapse).
  assert gate_spec_batch(50.0) is None
  assert gate_spec_batch(0.05) is None
  assert gate_spec_batch(None) is None


def test_spec_ngram_gate_keeps_plausible_ratios():
  """ISSUE 12: the draft-free n-gram/plain A/B ratio lives in ~[0.5, 9] —
  parity-ish at the adaptive floor, up to ~gamma+1 (benched depth 8) when
  on-stream rounds keep full acceptance on the repetition-heavy workload."""
  from bench import gate_spec_ngram

  assert gate_spec_ngram(1.0) == 1.0
  assert gate_spec_ngram(0.6) == 0.6
  assert gate_spec_ngram(4.2) == 4.2
  assert gate_spec_ngram(11.5) == 11.5


def test_spec_ngram_gate_drops_artifacts():
  from bench import gate_spec_ngram

  assert gate_spec_ngram(60.0) is None
  assert gate_spec_ngram(0.05) is None
  assert gate_spec_ngram(None) is None


def test_spec_policy_verdicts_pinned():
  """The proposer-policy dispatch verdicts bench emits on EVERY round
  (non-null on CPU, the paged_tile_* pattern): a collapsed model proposer
  switches to the untried n-gram, two measured-dead proposers fall back to
  plain, and re-probes prefer the free proposer."""
  from xotorch_support_jetson_tpu.inference.paging import spec_reprobe_proposer, spec_select_proposer

  assert spec_select_proposer("model", {"model": 0.1}, ("model", "ngram"))[0] == "ngram"
  assert spec_select_proposer("model", {"model": 0.1, "ngram": 0.05}, ("model", "ngram"))[0] == "plain"
  assert spec_reprobe_proposer({}, ("ngram", "model")) == "ngram"


def test_driver_wrapped_r02_artifact_is_filtered():
  """The poisoned record as the driver stored it — the bench line wrapped
  under "parsed", round-tripped through JSON — is neutralized by the same
  unwrap-then-filter bench.py applies to a previous round's file."""
  rec = json.loads(json.dumps({"n": 2, "parsed": R02}))
  if "parsed" in rec:
    rec = rec["parsed"]
  v = plausible_value(rec)
  assert v is not None and v < 1000.0, "poisoned r02 headline leaked through the filter"


def test_disagg_gate_keeps_plausible_values():
  """ISSUE 10: the disagg round's emitted numbers (burst TTFT ms, resident
  ITL ratio disagg/colocated, KV-transfer GB/s) ride the same drift-gate
  pattern as gate_kv_tier — generous plausibility bands, custom per field."""
  assert gate_disagg(114.5, lo=0.01, hi=600000.0) == 114.5
  assert gate_disagg(0.85, lo=0.001, hi=1000.0) == 0.85
  assert gate_disagg(1.2, lo=0.001, hi=1000.0) == 1.2  # >1 is reportable, not an artifact
  assert gate_disagg(3.5, lo=1e-6, hi=10000.0) == 3.5


def test_disagg_gate_drops_artifacts():
  assert gate_disagg(None) is None
  assert gate_disagg(0.0) is None  # a zero latency/rate is a broken fixture
  assert gate_disagg(-2.0, lo=0.001, hi=1000.0) is None
  assert gate_disagg(1e9, lo=0.01, hi=600000.0) is None
  assert gate_disagg(2000.0, lo=0.001, hi=1000.0) is None


def test_router_gate_keeps_plausible_values():
  """ISSUE 13: the router round's three fields ride one named gate with
  per-field bounds — the affine/random TTFT ratio (honest values include
  regressions above 1.0, recorded so drift is visible against the < 1.0
  target), the prefix hit rate fraction, and the failover splice window
  (same band as gate_failover: a sub-ms splice means a token raced the
  kill)."""
  from bench import gate_router

  assert gate_router(0.43, lo=0.001, hi=100.0) == 0.43
  assert gate_router(1.3, lo=0.001, hi=100.0) == 1.3  # a regression is a result, not an artifact
  assert gate_router(0.5, lo=0.0, hi=1.0) == 0.5
  assert gate_router(1.0, lo=0.0, hi=1.0) == 1.0  # every routed request affine is legitimate
  assert gate_router(0.0, lo=0.0, hi=1.0) == 0.0  # a dead-affinity round is a result, not an artifact
  assert gate_router(32.6, lo=1.0, hi=120000.0) == 32.6
  assert gate_router(4000.0, lo=1.0, hi=120000.0) == 4000.0


def test_router_gate_drops_artifacts():
  from bench import gate_router

  assert gate_router(None) is None
  assert gate_router(0.0, lo=0.001, hi=100.0) is None  # broken denominator
  assert gate_router(500.0, lo=0.001, hi=100.0) is None
  assert gate_router(1.2, lo=0.0, hi=1.0) is None  # a >1 hit "rate" is a counter bug
  assert gate_router(0.2, lo=1.0, hi=120000.0) is None  # token raced the kill
  assert gate_router(500000.0, lo=1.0, hi=120000.0) is None  # wedged into an outer timeout


def test_mixed_gate_keeps_plausible_values():
  """ISSUE 14: the mixed-tick round's fields ride one named gate with
  per-field bounds — the mid-burst resident ITL means (and amortized
  p50s), their mixed/alternating ratio (honest values include regressions
  above 1.0, recorded so drift is visible against the ≤ 0.5 acceptance
  bar), and the burst TTFT p50s."""
  from bench import gate_mixed

  assert gate_mixed(4.253, lo=0.001, hi=600000.0) == 4.253  # the measured CPU-fixture mean
  assert gate_mixed(0.3956, lo=0.001, hi=1000.0) == 0.3956
  assert gate_mixed(1.2, lo=0.001, hi=1000.0) == 1.2  # a regression is a result, not an artifact
  assert gate_mixed(151.97, lo=0.01, hi=600000.0) == 151.97


def test_mixed_gate_drops_artifacts():
  from bench import gate_mixed

  assert gate_mixed(None) is None
  assert gate_mixed(0.0, lo=0.001, hi=1000.0) is None  # a zero ITL/ratio is a broken fixture
  assert gate_mixed(-1.0, lo=0.001, hi=1000.0) is None
  assert gate_mixed(5e6, lo=0.01, hi=600000.0) is None  # wedged into an outer timeout


def test_paged_b48_gate_keeps_plausible_ratios():
  """ISSUE 11: the paged-vs-dense B=48 ratio rides its own named gate
  (target >= 0.95 with the shape-aware kernel retune). Honest values —
  including regressions below target and modest paged WINS above 1.0 —
  stay recorded so drift is visible against the target."""
  from bench import gate_paged_b48

  assert gate_paged_b48(0.97) == 0.97
  assert gate_paged_b48(1.1) == 1.1
  assert gate_paged_b48(0.80) == 0.80  # the r5 gap: a real number, not an artifact
  assert gate_paged_b48(0.5) == 0.5


def test_paged_b48_gate_drops_artifacts():
  from bench import gate_paged_b48

  assert gate_paged_b48(None) is None
  assert gate_paged_b48(0.0) is None  # broken denominator
  assert gate_paged_b48(-1.0) is None
  assert gate_paged_b48(5.0) is None  # early-return artifact, not a 5x paging win


def test_lora_gate_keeps_plausible_values():
  """ISSUE 15: the multi-LoRA round's drift gate — the mixed-vs-base B=8
  throughput ratio and the swap-in latency ride generous plausibility
  bands; honest regressions (e.g. a ratio below the 0.5 acceptance bar)
  stay RECORDED so the drift is visible in the bench record."""
  assert gate_lora(1.18, lo=0.001, hi=100.0) == 1.18
  assert gate_lora(0.5, lo=0.001, hi=100.0) == 0.5
  assert gate_lora(0.31, lo=0.001, hi=100.0) == 0.31  # below the bar, still recorded
  assert gate_lora(2.05, lo=0.0001, hi=600000.0) == 2.05  # swap ms p50


def test_lora_gate_drops_artifacts():
  assert gate_lora(0.0, lo=0.001, hi=100.0) is None
  assert gate_lora(1e6, lo=0.001, hi=100.0) is None
  assert gate_lora(None) is None


def test_compile_gate_steady_band_is_exactly_zero():
  """ISSUE 19: the program-ledger round's drift gate. The DEFAULT band is
  the steady band [0, 0] — ``steady_state_compiles`` must be exactly zero
  (the no-recompile invariant measured over live dispatches), so any
  nonzero count drops to null and surfaces as a missing metric."""
  from bench import gate_compile

  assert gate_compile(0) == 0.0
  assert gate_compile(0.0) == 0.0
  assert gate_compile(1) is None  # a steady-state recompile happened: broken round
  assert gate_compile(3) is None
  assert gate_compile(-1) is None
  assert gate_compile(None) is None


def test_compile_gate_warmup_band_keeps_plausible_seconds():
  """``warmup_compile_s_total`` rides the same gate with a generous
  plausibility band; 0.0 is legal (XOT_TPU_PROGRAMS=0 disables the ledger
  without nulling the bench key)."""
  from bench import gate_compile

  assert gate_compile(0.0, lo=0.0, hi=3600.0) == 0.0
  assert gate_compile(0.8421, lo=0.0, hi=3600.0) == 0.8421
  assert gate_compile(120.0, lo=0.0, hi=3600.0) == 120.0
  assert gate_compile(7200.0, lo=0.0, hi=3600.0) is None  # wedged into an outer timeout
  assert gate_compile(None, lo=0.0, hi=3600.0) is None
