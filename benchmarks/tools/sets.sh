# The measurement a bound is set from, on the chip, all runs of a cell in one call:
#   bash benchmarks/tools/sets.sh <cell> <run_seconds> [runs a set] [traced runs]
# two sets of six runs (the same six seeds in both), then three traced runs, and
# for each metric each set's median and its spread (the distance between the
# quartiles of statistics.quantiles(n=4), as a share of the median).
W=$1; RS=$2; N=${3:-6}; T=${4:-3}
mkdir -p chiprun_out
OUT=chiprun_out/sets_$W.jsonl; : > $OUT
for set in 1 2; do for k in $(seq 1 $N); do
  s=$((2147483648 + k * 1000003))
  python benchmarks/run.py --workload $W --seed $s --seconds $RS --trace 0 2>chiprun_out/last_err_$W.txt | tail -n 1 | sed "s/^/{\"set\": $set, \"seed\": $s, \"r\": /; s/$/}/" >> $OUT
  grep "^window\|^setup_s by\|^check correct" chiprun_out/last_err_$W.txt | tr '\n' ';' | cut -c1-700; echo
done; done
for k in $(seq 7 $((6 + T))); do
  s=$((2147483648 + k * 1000003))
  python benchmarks/run.py --workload $W --seed $s --seconds $RS --trace 1 2>chiprun_out/last_err_$W.txt | tail -n 1 | sed "s/^/{\"set\": 0, \"seed\": $s, \"r\": /; s/$/}/" >> $OUT
done
python - $OUT <<'PY'
import json, sys, statistics as st
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
print("runs", len(rows), "correct", [r["r"]["correct"] for r in rows], "failed", [r["r"]["failed"] for r in rows])
for name in rows[0]["r"]["metrics"]:
    for s in (1, 2):
        v = [r["r"]["metrics"][name]["value"] for r in rows if r["set"] == s]
        if name == "setup_s": v = v[1:] if s == 1 else v
        q = st.quantiles(v, n=4); med = st.median(v)
        print(f"{name} set {s}: median {med:.6g} spread {(q[2]-q[0])/med:.5f} min {min(v):.6g} max {max(v):.6g}")
tr = [r for r in rows if r["set"] == 0]
for r in tr:
    print("trace", r["seed"], r["r"]["correct"], {k: round(v["value"], 4) for k, v in r["r"]["metrics"].items()}, r["r"]["device"])
if tr: print("breakdown", json.dumps(tr[-1]["r"].get("breakdown"))[:1500])
PY
