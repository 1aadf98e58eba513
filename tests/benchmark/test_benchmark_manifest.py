"""BENCHMARK.json and every data file load and cross-refer, and adding a
cell, a configuration or a per-layer metric needs new files and one entry."""

import json
import os
import re

import pytest

from benchmarks.lib import manifest

import benchmark_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
ROOT = manifest.ROOT
BM = manifest.manifest()
CELLS = [w["name"] for w in BM["workloads"]]
E2E = {m["name"]: m for m in BM["end_to_end"]}


def _cells_of(metric):
    return metric.get("workloads", CELLS)


def test_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in BM[k]]
        assert len(ns) == len(set(ns))
    ms = [x["name"] for x in BM["end_to_end"] + BM["per_layer"]]
    assert len(ms) == len(set(ms))
    assert 1 <= BM["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_four_chip_cells_are_a_quarter_at_most():
    four = [w for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BM["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in BM["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    c = manifest.Cell(cell)
    assert c.traffic["kind"] in ("train", "serve")
    assert c.own["limits"] and c.own["trace_seconds"] > 0
    assert len(c.entry["why"]) <= 200
    reported = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer()


@pytest.mark.parametrize("cfg", [c["name"] for c in BM["configs"]])
def test_config_file(cfg):
    entry = next(c for c in BM["configs"] if c["name"] == cfg)
    assert entry["file"].startswith("benchmarks/")
    data = manifest.load_json(os.path.join(ROOT, entry["file"]))
    assert data["reduced"] == entry["reduced"] == []
    assert data["embedding_dim"] == 128 and len(data["table_sizes"]) == 26
    assert any(w["config"] == cfg for w in BM["workloads"])


@pytest.mark.parametrize("metric", [m["name"] for m in BM["per_layer"]])
def test_per_layer_metric(metric):
    m = next(x for x in BM["per_layer"] if x["name"] == metric)
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    moved = E2E[m["moves"]]
    # every cell that reports the metric reports the end-to-end metric it moves
    assert set(_cells_of(m)) <= set(_cells_of(moved))
    spec = manifest.load_json(os.path.join(
        manifest.BENCH, "metrics", metric + ".json"))
    assert os.path.isfile(os.path.join(
        manifest.BENCH, "readers", spec["reader"] + ".py"))
    if metric.endswith("_roofline") or "mfu" in metric:
        assert m["unit"] == "%"


def test_every_end_to_end_bound():
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert E2E["setup_s"]["bound"] == 0.1 and "workloads" not in E2E["setup_s"]


def test_layers_are_named_in_perf_md():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    for m in BM["per_layer"]:
        assert m["layer"] in text, m["layer"]


def test_adding_a_cell_a_config_and_a_metric_edits_no_file(tmp_path):
    """A later PR's move, played in a temporary checkout: new files and one
    entry each in BENCHMARK.json, and the harness finds them by name."""
    root = benchmark_tiny.make(str(tmp_path))
    bench = os.path.join(root, "benchmarks")
    before = {}
    for d, _, fs in os.walk(bench):
        for f in fs:
            p = os.path.join(d, f)
            before[p] = os.path.getmtime(p), os.path.getsize(p)
    bm = manifest.manifest(root)
    cfg = manifest.load_json(os.path.join(bench, "configs", "dlrm-kaggle.json"))
    cfg["name"] = "dlrm-other"
    with open(os.path.join(bench, "configs", "dlrm-other.json"), "w") as f:
        json.dump(cfg, f)
    tr = manifest.load_json(os.path.join(bench, "traffic",
                                         "train_onehot_b65536.json"))
    tr["global_batch"] = 128
    with open(os.path.join(bench, "traffic", "train_b128.json"), "w") as f:
        json.dump(tr, f)
    with open(os.path.join(bench, "workloads", "other_train.json"), "w") as f:
        json.dump({"trace_seconds": 1, "limits": {"loss1": 0.1}}, f)
    with open(os.path.join(bench, "metrics", "other_steps.json"), "w") as f:
        json.dump({"reader": "other_steps"}, f)
    with open(os.path.join(bench, "readers", "other_steps.py"), "w") as f:
        f.write("def read(ctx, spec):\n    return float(ctx['steps'])\n")
    bm["configs"].append({"name": "dlrm-other", "source": "test",
                          "file": "benchmarks/configs/dlrm-other.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "other_train", "config": "dlrm-other",
                            "traffic": "train_b128", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "other_steps", "unit": "count",
                            "better": "higher", "source": "program_counter",
                            "layer": "step builder", "moves": "samples_per_s",
                            "workloads": ["other_train"]})
    for m in bm["end_to_end"]:
        if m["name"] == "samples_per_s":
            m["workloads"].append("other_train")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    rc, last, err = benchmark_tiny.run_cell(root, "other_train", 5, trace=1)
    assert rc == 0, err[-2000:]
    assert last["metrics"]["other_steps"]["value"] >= 1
    assert "lookup_ms" not in last["metrics"]
    for p, stamp in before.items():
        assert (os.path.getmtime(p), os.path.getsize(p)) == stamp, p


def test_a_configuration_without_a_family_is_an_error(tmp_path):
    root = benchmark_tiny.make(str(tmp_path))
    path = os.path.join(root, "benchmarks", "configs", "dlrm-kaggle.json")
    cfg = manifest.load_json(path)
    del cfg["family"]
    with open(path, "w") as f:
        json.dump(cfg, f)
    with pytest.raises(SystemExit, match="names no family"):
        manifest.Cell("kaggle_train_onehot", root)
    with pytest.raises(SystemExit, match="no family 'absent'"):
        manifest.load_family("absent", root)


# A second family of another shape, as a later PR would write it: token
# sequences through ONE small table into a two-layer classifier, no tables
# list, no numerical features, one learning rate, Adam on the dense side,
# leaves under names of its own, a WORK and a FLOPS entry of its own.
BAGS_FAMILY = '''
import dataclasses
import jax, jax.numpy as jnp, numpy as np, optax
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseSGD, init_hybrid_state, make_hybrid_train_step)
from benchmarks.lib.check import rel_gap
from benchmarks.lib.traffic import power_law_ids, rng_of
from benchmarks.lib.train import CHECK_STEPS

CONTROL_PRECISION, REFERENCE_FAULTS = "bfloat16", ()


@dataclasses.dataclass
class Built:
    de: object
    state: object


def _weights(config, seed):
    rng = np.random.default_rng([int(seed), 7])
    v, w, h, c = (config[k] for k in ("vocab", "width", "hidden", "classes"))
    f = lambda *s: rng.normal(0, 0.3, s).astype(np.float32)
    return f(v, w), {"up": {"w": f(w, h), "b": f(h)},
                     "head": {"w": f(h, c), "b": f(c)}}


def _logits(dense, rows, seq, precision="float32"):
    q = (lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)) \\
        if precision == "bfloat16" else (lambda x: x)
    x = q(rows).reshape(-1, seq, rows.shape[-1]).mean(axis=1)
    x = jax.nn.relu(q(x) @ q(dense["up"]["w"]) + dense["up"]["b"])
    return q(x) @ q(dense["head"]["w"]) + dense["head"]["b"]


def _loss(dense, rows, labels, seq, precision="float32"):
    z = _logits(dense, rows, seq, precision)
    return optax.softmax_cross_entropy_with_integer_labels(z, labels).mean()


def build(config, tr, seed):
    table, dense = _weights(config, seed)
    de = DistributedEmbedding(
        [{"input_dim": config["vocab"], "output_dim": config["width"],
          "combiner": None,
          "embeddings_initializer":
              lambda key, shape, dtype: jnp.asarray(table, dtype)}],
        world_size=1, dp_input=True)
    state = init_hybrid_state(de, SparseSGD(), dense, optax.adam(tr["lr"]),
                              jax.random.key(0))
    return Built(de, state)


def train_batches(config, tr, seed):
    rng = rng_of(seed, 1)
    n = tr["sequences"] * config["seq"]
    return [(power_law_ids(rng, config["vocab"], (n,), 1.05),
             rng.integers(0, config["classes"], tr["sequences"]).astype(
                 np.int32)) for _ in range(tr["distinct_batches"])]


def stage(built, batch):
    # one sequence's tokens lie side by side; the labels ride along tiled to
    # the tokens' leading dimension, which the step wants of every leaf
    ids, labels = batch
    return [jnp.asarray(ids)], jnp.asarray(np.repeat(labels, len(ids)
                                                     // len(labels)))


def train_step(built, tr):
    def loss_fn(dp, outs, labels):
        seq = outs[0].shape[0] // tr["sequences"]
        return _loss(dp, outs[0].astype(jnp.float32), labels[::seq], seq)
    return make_hybrid_train_step(built.de, loss_fn, optax.adam(tr["lr"]),
                                  SparseSGD(), lr_schedule=tr["lr"],
                                  with_metrics=False, telemetry=False)


def samples_per_step(config, tr):
    return tr["sequences"]      # a sample is a sequence


def first_steps(built, tr, step, staged, batches, seed):
    state, built.state = built.state, None
    out = {"losses": []}
    for k in range(CHECK_STEPS):
        loss, state = step(state, *staged[k])
        out["losses"].append(float(loss))
    dense = jax.device_get(state.dense_params)
    out["up"] = float(np.linalg.norm(dense["up"]["w"]))
    out["head"] = float(np.linalg.norm(dense["head"]["w"]))
    out["tokens"] = float(np.linalg.norm(
        built.de.get_weights(state.emb_params)[0]))
    return out, state


def reference_numbers(config, tr, batches, seed, precision="float32",
                      fault=None):
    table, dense = _weights(config, seed)
    table = jnp.asarray(table)
    tx = optax.adam(tr["lr"])
    opt = tx.init(dense)
    out = {"losses": []}
    for ids, labels in batches[:CHECK_STEPS]:
        def f(dense, table):
            return _loss(dense, table[ids], labels, config["seq"], precision)
        loss, (gd, gt) = jax.value_and_grad(f, argnums=(0, 1))(dense, table)
        upd, opt = tx.update(gd, opt, dense)
        dense, table = optax.apply_updates(dense, upd), table - tr["lr"] * gt
        out["losses"].append(float(loss))
    out["up"] = float(jnp.linalg.norm(dense["up"]["w"]))
    out["head"] = float(jnp.linalg.norm(dense["head"]["w"]))
    out["tokens"] = float(jnp.linalg.norm(table))
    return out


def train_numbers(prog, ref):
    out = {f"loss{k + 1}": rel_gap(prog["losses"][k], ref["losses"][k])
           for k in range(CHECK_STEPS)}
    out.update({"norm3_" + k: rel_gap(prog[k], ref[k])
                for k in ("up", "head", "tokens")})
    return out


def step_work(config, tr, batches):
    return {"tokens_per_step": float(len(batches[0][0]))}


def _flops(config):
    w, h, c = config["width"], config["hidden"], config["classes"]
    return 3.0 * 2 * (w * h + h * c)


WORK = {"classifier": lambda cfg, w, ctx: (
    _flops(cfg) * ctx["samples"] / ctx["steps"], 0.0)}
FLOPS = {"classifier": _flops}
'''


def test_adding_a_model_family_edits_no_file(tmp_path):
    """The next ``model_config`` PR's move, played in a temporary checkout: a
    family of another shape, its configuration, traffic, workload, overlay and
    two metric files and the entries, and the harness runs the cell."""
    root = benchmark_tiny.make(str(tmp_path))
    before = {}
    for top in (os.path.join(root, "benchmarks"),
                os.path.dirname(os.path.abspath(__file__))):
        for d, _, fs in os.walk(top):
            for f in fs if "__pycache__" not in d else ():
                p = os.path.join(d, f)
                before[p] = os.path.getmtime(p), os.path.getsize(p)
    bench = os.path.join(root, "benchmarks")

    def write(rel, data):
        os.makedirs(os.path.dirname(os.path.join(bench, rel)), exist_ok=True)
        with open(os.path.join(bench, rel), "w") as f:
            f.write(data if isinstance(data, str) else json.dumps(data))

    write("families/bags.py", BAGS_FAMILY)
    write("configs/bags-small.json", {
        "name": "bags-small", "family": "bags", "vocab": 50_000, "width": 256,
        "hidden": 1024, "classes": 64, "seq": 128, "chips": 1, "reduced": []})
    write("tiny/configs/bags-small.json", {
        "vocab": 300, "width": 16, "hidden": 32, "classes": 8, "seq": 8})
    write("traffic/bags_b4096.json", {
        "kind": "train", "sequences": 4096, "distinct_batches": 8,
        "lr": 0.01})
    write("tiny/traffic/bags_b4096.json", {"sequences": 32,
                                           "distinct_batches": 4})
    write("workloads/bags_train.json", {
        "trace_seconds": 4, "limits": {"loss1": 1e-6, "loss3": 1e-6}})
    write("tiny/workloads/bags_train.json", {
        "trace_seconds": 1,
        "limits": {"loss1": 1e-3, "loss2": 1e-3, "loss3": 1e-3,
                   "norm3_up": 1e-3, "norm3_head": 1e-3,
                   "norm3_tokens": 1e-3}})
    write("metrics/classifier_roofline.json", {
        "reader": "roofline", "scopes": ["dense_forward_backward"],
        "work": "classifier", "per": "steps"})
    write("metrics/bags_step_mfu.json", {"reader": "mfu",
                                         "flops": "classifier"})
    bm = manifest.manifest(root)
    bm["configs"].append({"name": "bags-small", "source": "test",
                          "file": "benchmarks/configs/bags-small.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "bags_train", "config": "bags-small",
                            "traffic": "bags_b4096", "chips": 1,
                            "why": "test"})
    for name in ("classifier_roofline", "bags_step_mfu"):
        bm["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "dense fwd/bwd",
            "moves": "samples_per_s", "workloads": ["bags_train"]})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in ("samples_per_s", "compiles_in_window",
                         "device_step_ms"):
            m["workloads"].append("bags_train")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    benchmark_tiny.apply_overlays(root)
    cell = manifest.Cell("bags_train", root)
    assert cell.config["vocab"] == 300 and cell.traffic["sequences"] == 32
    for trace in (0, 1):
        rc, last, err = benchmark_tiny.run_cell(root, "bags_train", 2**31 + 3,
                                                trace)
        assert rc == 0, err[-3000:]
        assert last["correct"] is True and last["failed"] == 0, err[-2000:]
        assert last["attempted"] > 0
        assert set(last["compared"]) == set(cell.own["limits"])
        if trace:
            # a rehearsal has no device trace: both readers ran through the
            # family's WORK and FLOPS and found nothing to read
            assert last["metrics"]["compiles_in_window"]["value"] == 0
            assert not {"classifier_roofline", "bags_step_mfu",
                        "lookup_roofline"} & set(last["metrics"])
        else:
            assert set(last["metrics"]) == {"samples_per_s", "peak_hbm_gib",
                                            "setup_s"}
    # on a trace from the chip the two readers count the family's own work
    from benchmarks.lib import peaks, tracered
    fam = manifest.load_family("bags", root)
    trace = tracered.load(os.path.join(bench, "testdata",
                                       "train_onehot_3steps.trace.json.gz"))
    ctx = {"trace": trace, "steps": 3, "samples": 96, "window_s": 0.3,
           "chips": 1, "config": cell.config, "family": fam,
           "peaks": peaks.of("TPU v5 lite"),
           "work": fam.step_work(cell.config, cell.traffic, [([0] * 256,)])}
    flops = 3.0 * 2 * (16 * 32 + 32 * 8)
    assert manifest.read_metric("bags_step_mfu", ctx, root) \
        == pytest.approx(100 * flops * 96 / 0.3 / 197e12)
    assert manifest.read_metric("classifier_roofline", ctx, root) \
        == pytest.approx(100 * flops * 32 / 197e12 / 22.481e-3, rel=1e-3)
    with open(os.path.join(bench, "metrics", "classifier_roofline.json"),
              "w") as f:
        json.dump({"reader": "roofline", "scopes": ["dense_forward_backward"],
                   "work": "lookup", "per": "steps"}, f)
    with pytest.raises(SystemExit, match="no work function 'lookup'"):
        manifest.read_metric("classifier_roofline", ctx, root)
    for p, was in before.items():
        assert (os.path.getmtime(p), os.path.getsize(p)) == was, p
