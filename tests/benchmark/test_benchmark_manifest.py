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


def check_config_entry(root, entry, bm):
    """What holds of every configuration, whatever its family: the entry of
    ``configs``, the file it names and the workloads that run it agree, and a
    cut (``reduced``) is written down with what was published and the
    deployment it stands for. Every failure names the entry and the key."""
    name = entry["name"]

    def need(ok, key, what):
        assert ok, f"config {name!r}, {key!r}: {what}"

    need(entry["file"].startswith("benchmarks/configs/"), "file",
         f"{entry['file']} is not under benchmarks/configs/")
    path = os.path.join(root, entry["file"])
    need(os.path.isfile(path), "file", f"{entry['file']} is not there")
    data = manifest.load_json(path)
    need(data.get("name") == name, "name",
         f"the file calls itself {data.get('name')!r}")
    family = data.get("family")
    need(isinstance(family, str) and family, "family",
         "the file names no family")
    base = os.path.join(root, "benchmarks", "families", family)
    need(os.path.isfile(base + ".py")
         or os.path.isfile(os.path.join(base, "__init__.py")), "family",
         f"neither benchmarks/families/{family}.py nor {family}/__init__.py")
    cells = [w for w in bm["workloads"] if w["config"] == name]
    need(cells, "workloads", "no workload runs it")
    need(data.get("chips") in (1, 4), "chips",
         f"the file says {data.get('chips')!r}, not 1 or 4")
    for w in cells:
        need(w["chips"] == data["chips"], "chips",
             f"the file says {data['chips']}, workload {w['name']!r} "
             f"{w['chips']}")
    source = entry.get("source")
    need(isinstance(source, str) and 1 <= len(source) <= 200, "source",
         f"{len(source) if isinstance(source, str) else source!r} characters,"
         " not 1 to 200")
    reduced = data.get("reduced")
    need(isinstance(reduced, list) and len(reduced) <= 16
         and all(isinstance(k, str) for k in reduced), "reduced",
         f"the file's is {reduced!r}, not a list of at most 16 keys")
    need(reduced == entry["reduced"], "reduced",
         f"the file says {reduced}, the entry {entry['reduced']}")
    for k in reduced:
        need(NAME.match(k) and k in data, k,
             "reduced names a key that the file lacks")
        need(not k.endswith(("_dim", "_rank")), k,
             "a width is never reduced")
    if reduced:
        published = data.get("published")
        need(isinstance(published, dict), "published",
             "reduced is not empty and the file has no published values")
        for k in reduced:
            need(k in published, k, "published lacks the source's own value")
            need(published[k] != data[k], k,
                 f"published holds the file's own value {data[k]!r}: "
                 "nothing was reduced")
        deployment = data.get("deployment")
        need(isinstance(deployment, str) and deployment.strip(), "deployment",
             "reduced is not empty and the file states no deployment")
    if "assumed" in data:
        need(isinstance(data["assumed"], list)
             and all(isinstance(a, str) for a in data["assumed"]), "assumed",
             "not a list of strings")
    return data


def _family_of(entry):
    return manifest.load_json(os.path.join(ROOT, entry["file"])).get("family")


@pytest.mark.parametrize("cfg", [c["name"] for c in BM["configs"]])
def test_config_file(cfg):
    entry = next(c for c in BM["configs"] if c["name"] == cfg)
    check_config_entry(ROOT, entry, BM)


@pytest.mark.parametrize("cfg", [c["name"] for c in BM["configs"]
                                 if _family_of(c) == "dlrm"])
def test_dlrm_config_file(cfg):
    """What holds of the DLRM configurations and of no other family's."""
    entry = next(c for c in BM["configs"] if c["name"] == cfg)
    data = manifest.load_json(os.path.join(ROOT, entry["file"]))
    assert data["reduced"] == entry["reduced"] == []
    assert data["embedding_dim"] == 128 and len(data["table_sizes"]) == 26


# a malformed entry or file, by name: (what makes it so, the key that the
# failure has to name)
MALFORMED = {}


def _malformed(key):
    def register(mutate):
        MALFORMED[mutate.__name__] = mutate, key
    return register


@_malformed("reduced")
def reduced_differs(data, entry, bm):
    entry["reduced"] = ["layers"]


@_malformed("experts")
def reduced_key_absent(data, entry, bm):
    del data["experts"]


@_malformed("published")
def no_published(data, entry, bm):
    del data["published"]


@_malformed("experts")
def published_lacks_a_key(data, entry, bm):
    del data["published"]["experts"]


@_malformed("layers")
def published_is_the_files_value(data, entry, bm):
    data["published"]["layers"] = data["layers"]


@_malformed("deployment")
def no_deployment(data, entry, bm):
    del data["deployment"]


@_malformed("head_dim")
def a_width_reduced(data, entry, bm):
    data["head_dim"], data["published"]["head_dim"] = 64, 128
    data["reduced"].append("head_dim")
    entry["reduced"].append("head_dim")


@_malformed("source")
def source_of_201_characters(data, entry, bm):
    entry["source"] = "x" * 201


@_malformed("workloads")
def no_workload_runs_it(data, entry, bm):
    bm["workloads"].clear()


@_malformed("chips")
def chips_disagree(data, entry, bm):
    bm["workloads"][0]["chips"] = 4


@_malformed("family")
def family_absent(data, entry, bm):
    data["family"] = "absent"


@_malformed("name")
def another_name(data, entry, bm):
    data["name"] = "toy-whole"


@_malformed("assumed")
def assumed_is_no_list(data, entry, bm):
    data["assumed"] = "random weights"


@pytest.mark.parametrize("case", ["sound"] + sorted(MALFORMED))
def test_a_malformed_configuration_is_named(tmp_path, case):
    """``check_config_entry`` over the least checkout that it reads: one cut
    configuration of a family of its own, its entry and its one workload."""
    root = str(tmp_path)
    for d in ("configs", "families"):
        os.makedirs(os.path.join(root, "benchmarks", d))
    open(os.path.join(root, "benchmarks", "families", "toy.py"), "w").close()
    data = {"name": "toy-cut", "family": "toy", "chips": 1, "layers": 6,
            "experts": 16, "reduced": ["layers", "experts"],
            "published": {"layers": 64, "experts": 256},
            "deployment": "16 chips share each layer: 16 of 256 experts and "
                          "6 of 64 layers here",
            "assumed": ["weights are random from the seed"]}
    entry = {"name": "toy-cut", "source": "https://example.org/toy",
             "file": "benchmarks/configs/toy-cut.json",
             "reduced": ["layers", "experts"], "why": "test"}
    bm = {"configs": [entry], "workloads": [
        {"name": "toy_serve", "config": "toy-cut", "traffic": "toy",
         "chips": 1, "why": "test"}]}
    mutate, key = MALFORMED.get(case, (None, None))
    if mutate:
        mutate(data, entry, bm)
    with open(os.path.join(root, entry["file"]), "w") as f:
        json.dump(data, f)
    if key is None:
        assert check_config_entry(root, entry, bm) == data
    else:
        with pytest.raises(AssertionError, match=re.escape(
                f"config 'toy-cut', {key!r}")):
            check_config_entry(root, entry, bm)


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
# leaves under names of its own, a WORK and a FLOPS entry of its own, a
# counter of its own that its step's host wrapper bumps, and the same
# classifier served: a request is a few sequences, a sample one sequence, an
# answer the logits of every class.
BAGS_FAMILY = '''
import dataclasses
import jax, jax.numpy as jnp, numpy as np, optax
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, ServeConfig, ServingRuntime, SparseSGD,
    init_hybrid_state, make_hybrid_train_step)
from distributed_embeddings_tpu.utils import obs
from benchmarks.families import system
from benchmarks.lib.check import rel_gap
from benchmarks.lib.traffic import arrivals, power_law_ids, rng_of
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
    state = init_hybrid_state(de, SparseSGD(), dense,
                              optax.adam(tr.get("lr", 0.0)),
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
    step = make_hybrid_train_step(built.de, loss_fn, optax.adam(tr["lr"]),
                                  SparseSGD(), lr_schedule=tr["lr"],
                                  with_metrics=False, telemetry=False)

    def counted(state, *staged):
        # a counter of the family's own, among the program's: the runner
        # takes its rise over the window into ctx["counters"]
        obs.counter_inc("bags_sequences", tr["sequences"])
        return step(state, *staged)
    return counted


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


@dataclasses.dataclass
class Schedule:
    due_s: np.ndarray
    offsets: np.ndarray
    tokens: np.ndarray      # [sequences, seq]

    def __len__(self):
        return len(self.due_s)

    def request(self, i):
        # one categorical input of [sequences, seq] ids and no dense batch
        return [self.tokens[self.offsets[i]:self.offsets[i + 1]]], None


def serving_runtime(built, serve):
    def pred_fn(dense, outs, batch):
        rows = outs[0].astype(jnp.float32)      # [sequences, seq, width]
        return _logits(dense, rows, rows.shape[1])
    return ServingRuntime(
        built.de, pred_fn, built.state, trace=False, config=ServeConfig(
            rungs=serve["rungs"], max_wait_ms=serve["max_wait_ms"],
            deadline_ms=serve["deadline_ms"], max_queue=serve["max_queue"],
            shed_frac=serve["shed_frac"]))


def serve_schedule(config, tr, seed, seconds):
    rng = rng_of(seed, 2)
    due, offsets = arrivals(tr, rng, seconds)
    return Schedule(due, offsets, power_law_ids(
        rng, config["vocab"], (int(offsets[-1]), config["seq"]), 1.05))


def requests_of(schedule):
    return [system.Request(cats=schedule.request(i)[0])
            for i in range(len(schedule))]


def reference_answers(config, schedule, picked, seed, precision="float32"):
    table, dense = _weights(config, seed)
    ids = np.concatenate([schedule.request(i)[0][0] for i in picked])
    with jax.default_matmul_precision("highest"):
        return np.asarray(_logits(dense, jnp.asarray(table)[ids.reshape(-1)],
                                  config["seq"], precision))


def serve_numbers(schedule, results, picked, want):
    gap, misshapen, a = 0.0, 0, 0
    for i in picked:
        n = int(schedule.offsets[i + 1] - schedule.offsets[i])
        got = np.asarray(results[i].predictions, np.float32)
        if got.shape != want[a:a + n].shape or not np.isfinite(got).all():
            misshapen += 1
        else:
            gap = max(gap, float(np.abs(got - want[a:a + n]).max()))
        a += n
    return {"logit_gap": gap, "misshapen": float(misshapen)}


def _flops(config):
    w, h, c = config["width"], config["hidden"], config["classes"]
    return 3.0 * 2 * (w * h + h * c)


WORK = {"classifier": lambda cfg, w, ctx: (
    _flops(cfg) * ctx["samples"] / ctx["steps"], 0.0)}
FLOPS = {"classifier": _flops}
'''


class _Play:
    """A temporary checkout at toy sizes into which a test writes what a later
    PR would add: family ``bags``, its configuration CUT as the guide's
    section 4 has it (an eighth of a stated vocabulary, ``reduced``,
    ``published`` and ``deployment`` written down) and the configuration's
    entry. ``finish`` writes ``BENCHMARK.json``, applies the overlays and
    holds every entry of ``configs`` to ``check_config_entry``;
    ``untouched`` sees that no file that was there was edited."""

    def __init__(self, tmp_path):
        self.root = benchmark_tiny.make(str(tmp_path))
        self.bench = os.path.join(self.root, "benchmarks")
        self.before = {}
        for top in (self.bench, os.path.dirname(os.path.abspath(__file__))):
            for d, _, fs in os.walk(top):
                for f in fs if "__pycache__" not in d else ():
                    p = os.path.join(d, f)
                    self.before[p] = os.path.getmtime(p), os.path.getsize(p)
        self.bm = manifest.manifest(self.root)
        self.write("families/bags.py", BAGS_FAMILY)
        self.write("configs/bags-small.json", {
            "name": "bags-small", "family": "bags", "vocab": 6_250,
            "width": 256, "hidden": 1024, "classes": 64, "seq": 128,
            "chips": 1, "reduced": ["vocab"], "published": {"vocab": 50_000},
            "deployment": "8 chips share the table by rows: an eighth of the "
                          "vocabulary here, the ids drawn from that slice",
            "assumed": ["weights are random from the seed"]})
        self.write("tiny/configs/bags-small.json", {
            "vocab": 300, "width": 16, "hidden": 32, "classes": 8, "seq": 8})
        self.bm["configs"].append({
            "name": "bags-small", "source": "test",
            "file": "benchmarks/configs/bags-small.json",
            "reduced": ["vocab"], "why": "test"})

    def write(self, rel, data):
        path = os.path.join(self.bench, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(data if isinstance(data, str) else json.dumps(data))

    def cell(self, name, traffic, reports):
        """The cell's entry, and its name appended to the ``workloads`` of
        every metric in ``reports`` that is there already."""
        self.bm["workloads"].append({
            "name": name, "config": "bags-small", "traffic": traffic,
            "chips": 1, "why": "test"})
        for m in self.bm["end_to_end"] + self.bm["per_layer"]:
            if m["name"] in reports:
                m["workloads"].append(name)

    def metric(self, name, cell, **entry):
        self.bm["per_layer"].append(dict(
            {"name": name, "better": "higher", "workloads": [cell]}, **entry))

    def finish(self):
        with open(os.path.join(self.root, "BENCHMARK.json"), "w") as f:
            json.dump(self.bm, f)
        benchmark_tiny.apply_overlays(self.root)
        for entry in self.bm["configs"]:
            check_config_entry(self.root, entry, self.bm)

    def untouched(self):
        for p, was in self.before.items():
            assert (os.path.getmtime(p), os.path.getsize(p)) == was, p


def test_adding_a_model_family_edits_no_file(tmp_path):
    """The next ``model_config`` PR's move, played in a temporary checkout: a
    family of another shape, its cut configuration, traffic, workload, overlay
    and three metric files and the entries, and the harness runs the cell."""
    play = _Play(tmp_path)
    root, bench = play.root, play.bench
    play.write("traffic/bags_b4096.json", {
        "kind": "train", "sequences": 4096, "distinct_batches": 8,
        "lr": 0.01})
    play.write("tiny/traffic/bags_b4096.json", {"sequences": 32,
                                                "distinct_batches": 4})
    play.write("workloads/bags_train.json", {
        "trace_seconds": 4, "limits": {"loss1": 1e-6, "loss3": 1e-6}})
    play.write("tiny/workloads/bags_train.json", {
        "trace_seconds": 1,
        "limits": {"loss1": 1e-3, "loss2": 1e-3, "loss3": 1e-3,
                   "norm3_up": 1e-3, "norm3_head": 1e-3,
                   "norm3_tokens": 1e-3}})
    play.write("metrics/classifier_roofline.json", {
        "reader": "roofline", "scopes": ["dense_forward_backward"],
        "work": "classifier", "per": "steps"})
    play.write("metrics/bags_step_mfu.json", {"reader": "mfu",
                                              "flops": "classifier"})
    # the family's own counter reaches a metric as a data file alone
    play.write("metrics/bags_sequences.json", {
        "reader": "value", "group": "counters", "key": "bags_sequences"})
    play.cell("bags_train", "bags_b4096",
              ("samples_per_s", "compiles_in_window", "device_step_ms"))
    for name in ("classifier_roofline", "bags_step_mfu"):
        play.metric(name, "bags_train", unit="%", source="device_trace",
                    layer="dense fwd/bwd", moves="samples_per_s")
    play.metric("bags_sequences", "bags_train", unit="count",
                source="program_counter", layer="step builder",
                moves="samples_per_s")
    play.finish()
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
            # the counter rose by one step's sequences a window step: the
            # first three steps, before the window, are not in it
            assert last["metrics"]["bags_sequences"]["value"] \
                == 32 * last["attempted"]
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
    play.untouched()


def test_adding_a_serve_family_edits_no_file(tmp_path):
    """The same move for a cell of ``"kind": "serve"``: the ``bags``
    classifier behind ``ServingRuntime``, its answers held to the family's own
    float32 forward, a metric that is there gaining the cell and a new one
    that reads the runtime's own summary, ``ctx["stats"]``, as a data file."""
    play = _Play(tmp_path)
    play.write("traffic/bags_serve.json", {
        "kind": "serve", "rate_per_s": 400.0,
        "size_quantiles": {"p": [0, 0.5, 1.0], "samples": [1, 4, 32]},
        "serve": {"rungs": [64, 256], "max_wait_ms": 5, "deadline_ms": 2000,
                  "max_queue": 4000, "shed_frac": 1.0}})
    play.write("tiny/traffic/bags_serve.json", {
        "rate_per_s": 40.0,
        "size_quantiles": {"p": [0, 0.5, 1.0], "samples": [1, 3, 8]},
        "serve": {"rungs": [8, 32], "max_wait_ms": 5, "deadline_ms": 2000,
                  "max_queue": 4000, "shed_frac": 1.0}})
    play.write("workloads/bags_serve.json", {
        "trace_seconds": 4,
        "limits": {"logit_gap": 1e-4, "misshapen": 0, "unanswered": 0}})
    play.write("tiny/workloads/bags_serve.json", {"trace_seconds": 1})
    play.write("metrics/bags_flushes.json", {
        "reader": "value", "group": "stats", "key": "flushes"})
    play.cell("bags_serve", "bags_serve",
              ("serve_p50_ms", "served_samples_per_s", "serve_recompiles",
               "serve_queue_wait_ms_p99"))
    play.metric("bags_flushes", "bags_serve", unit="count",
                source="program_counter", layer="serving",
                moves="serve_p50_ms")
    play.finish()
    cell = manifest.Cell("bags_serve", play.root)
    assert cell.traffic["serve"]["rungs"] == [8, 32]
    for trace in (0, 1):
        rc, last, err = benchmark_tiny.run_cell(play.root, "bags_serve",
                                                2**31 + 4, trace)
        assert rc == 0, err[-3000:]
        assert last["correct"] is True and last["failed"] == 0, err[-2000:]
        assert last["attempted"] == 40      # a second at 40 requests/s
        assert set(last["compared"]) == {"logit_gap", "misshapen",
                                         "unanswered"}
        if trace:
            assert set(last["metrics"]) == {
                "serve_recompiles", "serve_queue_wait_ms_p99", "bags_flushes"}
            assert last["metrics"]["serve_recompiles"]["value"] == 0
            assert 1 <= last["metrics"]["bags_flushes"]["value"] <= 40
        else:
            assert set(last["metrics"]) == {
                "serve_p50_ms", "served_samples_per_s", "peak_hbm_gib",
                "setup_s"}
    play.untouched()
