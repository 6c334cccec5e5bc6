"""Test harness config: the CPU backend with 8 virtual devices.

The tests check results and counts, which need no accelerator, and the
sharding/pipeline tests need a mesh, which 8 virtual CPU devices give. The
platform is set the one way the package honours (``JAX_PLATFORMS``,
utils/helpers.py ``apply_platform_override``) and before jax is imported
anywhere, hence the env mutation at module import time. The chip is reached
by ``chip_smoke.py``, never by a test.
"""

import asyncio
import inspect
import os
import tempfile

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
  os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("XOT_TPU_UUID", "test-node-id")
os.environ.setdefault("HF_HUB_OFFLINE", "1")  # no egress in CI; fail fast
# Incident auto-captures (ISSUE 9: stall watchdog / anomaly watchers inside
# cluster tests) must never write into the real $XOT_HOME from CI.
os.environ.setdefault("XOT_TPU_BUNDLE_DIR", "/tmp/xot-test-bundles")
# The n-gram proposer (ISSUE 12) makes XOT_TPU_SPEC_BATCH=auto speculate
# DRAFT-FREE — the production default. In the suite that would flip every
# batched greedy test onto the spec programs (one extra compiled program
# per module for streams that are already identity-pinned), so the suite
# pins the family OFF here; tests/test_spec_ngram.py turns it on explicitly
# and pins the draft-free behavior end to end.
os.environ.setdefault("XOT_TPU_SPEC_NGRAM", "0")

import jax  # noqa: E402

# What a test costs is the programs it compiles (two thirds of a model kind's
# module is XLA:CPU compiling), and modules build many of the same ones: the
# suite's workers share one persistent compile cache under the system's
# temporary directory (the driver gives a run a TMPDIR of its own, so its runs
# start with the directory empty). A key is the program's own bytes, its compile
# options and the compiler's version, so an entry is never stale; op metadata is
# not in the key, so a module that compiles one program under two sets of scope
# names, or for a described (absent) chip whose entries cannot be read back,
# asks for ``no_persistent_compile_cache``. Daemons and subprocesses that tests
# start get a cache directory of their own.
jax.config.update("jax_compilation_cache_dir", os.path.join(tempfile.gettempdir(), "xot-test-compile-cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)  # most of the suite's programs compile in under JAX's one second
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


pytest.register_assert_rewrite("served_kind")  # the served-kind battery's cases live there; its ``assert``s keep their messages


# The order the suite's modules are handed to the driver's six workers in (``-n 6 --dist loadfile``: a module a worker
# at a time, the next as a worker runs dry). Left alone, xdist hands out the modules of most tests first, so a module of
# thirteen compiles of a cell's whole program (530 CPU-seconds) and the benchmark's rehearsals (a server and its callers
# in processes of their own, 100-180 s each) start last, all at once, on cores they then fight over — a run ends when
# they do, and a rehearsal that meets a busy machine finds no decode step in its capture. So: the long first (a run can
# end no sooner than its longest chain, started last), and of the modules that start servers or compile for the TPU
# with many threads at most two at a time. Made from a whole run's junit file (CPU-seconds a module, longest first, a
# loud module passed over while two are running); a module not named keeps its place after these, none over 60 s.
LONG_FIRST = (
  "test_tpu_compile_cells", "test_moe", "test_hybrid_kda_moe_kind", "test_swa_gqa_moe", "test_hybrid_kda", "test_paged",
  "test_hybrid_gdn_kind", "test_tpu_compile_smoke", "test_diffusion", "test_pp_batch", "test_hybrid_ssm", "test_spec_decode",
  "test_paged_int4", "test_hybrid_gdn", "test_pp_lifecycle", "test_tpu_compile", "test_hybrid_ssm_kind", "test_mixed_tick",
  "test_qkv_barrier", "test_hybrid_conv_moe", "test_hybrid_ssm_moe", "test_swa_nope_moe", "test_spec_ngram", "test_pp_serving", "test_spec_batch", "test_named_scopes", "test_image_api",
  "test_batched", "test_add_cell", "test_paged_pool_inplace", "test_ring_training", "test_sp_paged", "test_sp_serving",
  "test_experts_touched", "test_parallel", "test_hf_golden", "test_ssm_state_step", "test_kv_tier", "test_kv_quant",
)  # fmt: skip


def pytest_configure(config):
  config.addinivalue_line("markers", "asyncio: run test in an asyncio event loop")
  config.option.loadscopereorder = False  # xdist's own order is by count of tests; ``LONG_FIRST`` is by what they cost


def pytest_collection_modifyitems(items):
  rank = {name: i for i, name in enumerate(LONG_FIRST)}
  items.sort(key=lambda item: rank.get(item.path.stem, len(rank)))  # stable: the rest as collected


@pytest.fixture(scope="module")
def no_persistent_compile_cache():
  """The persistent compile cache off for one module, and as it was after it."""
  from jax.experimental.compilation_cache import compilation_cache

  was = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  yield
  jax.config.update("jax_enable_compilation_cache", was)
  compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _fp32_matmuls():
  """Numerical tests compare reduction orders; run matmuls in true fp32.

  (This build's DEFAULT matmul precision computes fp32 matmuls with bf16
  passes, which would swamp cache-vs-full equivalence at ~2^-8.)
  """
  import jax

  with jax.default_matmul_precision("highest"):
    yield


@pytest.fixture
def interpreted_paged_kernels(monkeypatch):
  """A decode program told ``use_kernel`` on this CPU: the paged kernels it traces — the latent body, the Mosaic token
  write — run interpreted (a decode program has no such argument). Yields the list the latent body's traces append to."""
  from xotorch_support_jetson_tpu.ops import paged

  attend, write, traced = paged.paged_latent_decode_attention, paged.write_token_kv, []
  monkeypatch.setattr(paged, "paged_latent_decode_attention", lambda *a, **kw: traced.append(1) or attend(*a, **kw, interpret=True))
  monkeypatch.setattr(paged, "write_token_kv", lambda *a: write(*a[:7], interpret=True))
  return traced


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
  """Minimal pytest-asyncio replacement (the plugin isn't in the image)."""
  fn = pyfuncitem.obj
  if inspect.iscoroutinefunction(fn):
    kwargs = {name: pyfuncitem.funcargs[name] for name in pyfuncitem._fixtureinfo.argnames}
    asyncio.run(fn(**kwargs))
    return True
  return None


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
  """Drop compiled executables between test modules.

  ~270 tests in one process accumulate hundreds of live XLA CPU executables;
  full-suite runs (and only full-suite runs — every module passes in
  isolation) intermittently segfault inside backend_compile_and_load under
  that load. Executables are rarely shared across modules (each uses its own
  tiny configs), so clearing costs little and keeps the native state small.
  """
  yield
  import jax

  jax.clear_caches()
