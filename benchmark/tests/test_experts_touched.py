"""``tools/experts_touched.py`` (PR 39), on the CPU: the exact count of a made-up table, the kinds' ``router_tables``
against the router weights that the same draw made, and the tool's rehearsal end to end through ``serve.py``."""

import json
import os
import subprocess
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

import arch  # noqa: E402
import common  # noqa: E402
import experts_touched  # noqa: E402
import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
import weights  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent.parent


def test_distinct_owned_counts_the_union_of_the_rows_topics_inside_the_counted_range():
  owns = np.zeros((2, 3, 8), bool)  # 2 expert layers, 3 topics, 8 routed experts
  owns[0, 0, [0, 1]], owns[0, 1, [1, 5]], owns[0, 2, [6, 7]] = True, True, True
  owns[1, 0, [2, 3]], owns[1, 1, [2, 3]], owns[1, 2, [0, 4]] = True, True, True
  topic_of = np.array([0, 0, 1, 2, 1])
  assert list(experts_touched.distinct_owned(owns, topic_of, [0, 1], 0, 8)) == [2, 2]  # two rows of one topic: the same two experts
  assert list(experts_touched.distinct_owned(owns, topic_of, [0, 2, 4], 0, 8)) == [3, 2]
  assert list(experts_touched.distinct_owned(owns, topic_of, [0, 2, 3], 0, 4)) == [2, 3]  # held: experts 0-3 only
  assert list(experts_touched.distinct_owned(owns, topic_of, [3], 4, 4)) == [2, 1]
  assert list(experts_touched.distinct_owned(owns, topic_of, [], 0, 8)) == [0, 0]


@pytest.mark.parametrize(
  "config, kind_name, stack, rows_of_the_table",
  # the rehearsal's four Ling layers: ssm_layers, ssm_moe_layers, moe_layers, ssm_moe_layers - expert layers 0 and 2 of the table's three
  [("ling-3.0-flash-ep4-d7", "hybrid_kda_moe", "ssm_moe_layers", [0, 2]), ("moonlight-a3b-d14", "mla_moe", "moe_layers", [0, 1])],
)
def test_router_tables_are_the_draw_that_made_the_router(config, kind_name, stack, rows_of_the_table):
  """The tables come from ``make_params``' own function and key: each topic owns k experts a layer, and the made
  router's columns carry the topic's direction exactly where the table says (gain x owns, the N(0, 1/D) part beside it)."""
  kind, hf = arch.load(kind_name), common.load_config(config)
  hf.update(kind.REHEARSE_WIDTHS)
  hf["router_topics"] = 4
  shapes, key = weights.shape_hf(hf), weights.seed_key(2147483659)
  tables = jax.jit(lambda k: kind.router_tables(shapes, k))(key)
  owns, topic_of = np.asarray(tables["owns"]), np.asarray(tables["topic_of"])
  _first, _counted, routed, top_k = kind.routed_experts(hf)
  assert owns.shape[1:] == (4, routed) and (owns.sum(axis=2) == top_k).all() and topic_of.shape == (hf["vocab_size"],) and set(topic_of) == {0, 1, 2, 3}
  params = weights.build_params(hf, 2147483659)
  w = np.asarray(params[stack]["w_router"], np.float32)  # [n, D, E]
  embed = np.asarray(params["embed"], np.float32)
  # a topic's direction, read back from the embedding rows of its tokens (their N(0, 1) parts average out)
  direction = np.sign(np.stack([embed[topic_of == t].mean(axis=0) for t in range(4)]))
  project = np.einsum("td,nde->nte", direction, w)  # gain x owns + the other topics' cross terms + noise of spread 1
  assert ((project > 10) == (owns[rows_of_the_table] > 0)).all()
  assert kind.router_tables({**shapes, "router_topics": 0}, key) is None


def test_the_tools_rehearsal_reads_rows_topics_and_routes():
  p = subprocess.run(
    [sys.executable, "benchmark/tools/experts_touched.py", "--workload", "moonlight-a3b.decode-closed", "--seed", "2147484001", "--seconds", "3", "--every", "0.5", "--rehearse"],
    cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900,
  )
  assert p.returncode == 0, p.stderr[-3000:]
  lines = [json.loads(line) for line in p.stdout.splitlines() if line.startswith("{")]
  result, layers = lines[-1], [x for x in lines if "expert_layer" in x]
  assert result["rehearsal"] and result["device"]["platform"] == "cpu" and result["requests_failed"] == 0 and result["instants"] >= 3
  assert len(layers) == 2 and 8 <= result["rows_mean"] <= 16 and 0 < result["exact_mean"] <= result["counted"] == 8
  assert result["expected"] <= result["uniform_routing_would_count"] and result["router"]["tokens"] > 16
  assert 0 < result["topics_distinct_mean"] <= result["rows_mean"]
