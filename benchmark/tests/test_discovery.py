"""A new configuration, traffic mix and metric are found by name.

The test copies the benchmark into a scratch checkout and ADDS, edits
no file that was there: a configuration with a second endpoint and a
policy generator of its own, a mix with a traffic shape and an arrival
loop of its own (connections spread over both endpoints, offered in
on/off bursts), a metric reader, and their entries in BENCHMARK.json.
It rehearses the new cell end to end on the CPU: the run must report
the new metric and come out correct.
"""

import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def test_new_files_are_picked_up_by_name(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.symlink(os.path.join(ROOT, "cilium_tpu"), tmp_path / "cilium_tpu")
    before = {p: p.read_bytes() for p in tmp_path.rglob("*")
              if p.is_file() and p.name != "BENCHMARK.json"}

    b = tmp_path / "benchmark"
    cfg = json.loads((b / "configs" / "east-west-10k.json").read_text())
    cfg["name"] = "two-endpoints"
    cfg["endpoints"].append({"name": "cache", "ip": "10.0.0.3",
                             "labels": ["k8s:app=cache"]})
    cfg["policy"] = {"generator": "db-and-cache", "rules": 4}
    (b / "configs" / "two-endpoints.json").write_text(json.dumps(cfg))
    (b / "policies" / "db-and-cache.py").write_text(
        "import byname\n"
        "def rules(n, params):\n"
        "    out = byname.load('policies', 'baseline-l3l4').rules(n, params)\n"
        "    return out + [{'endpointSelector': {'matchLabels':"
        " {'app': 'cache'}}, 'ingress': [{'fromEndpoints': [{'matchLabels':"
        " {'ns': 'default'}}], 'toPorts': [{'ports': [{'port': '5432',"
        " 'protocol': 'TCP'}]}]}]}]\n")
    (b / "shapes" / "crr-two.py").write_text(
        "import numpy as np\n"
        "import byname\n"
        "from traffic import COL_DST_IP3, COL_EP, COL_SPORT, COL_SRC_IP3\n"
        "class Shape(byname.load('shapes', 'crr').Shape):\n"
        "    def block(self, b, rng):\n"
        "        out = super().block(b, rng)\n"
        "        two = ((out[:, COL_SRC_IP3] ^ out[:, COL_SPORT]) & 1) == 1\n"
        "        ep = self.s.endpoints[1]\n"
        "        out[two, COL_DST_IP3] = ep['ip']\n"
        "        out[two, COL_EP] = ep['id']\n"
        "        return out\n")
    (b / "loops" / "burst.py").write_text(
        "import time\n"
        "import byname\n"
        "def run(gen):\n"
        "    mix = dict(gen.mix, rate_rows_per_s=2 * gen.mix['rate_rows_per_s'])\n"
        "    arr = byname.load('loops', 'open').Arrivals(mix, gen.s.seed)\n"
        "    t0 = time.monotonic()\n"
        "    while not gen.stopping():\n"
        "        on = arr.next()\n"
        "        due = t0 + on + 0.1 * int(on / 0.1)\n"
        "        if due > time.monotonic():\n"
        "            gen.idle(due - time.monotonic())\n"
        "        gen.offer(int(mix['chunk_rows']), due)\n")
    mix = {"name": "crr-burst-two", "shape": "crr-two", "loop": "burst",
           "packets_per_conn": 16, "open_conns": 1280, "port": 5432,
           "denied_port": 443, "denied_frac": 0.02, "chunk_rows": 256,
           "rate_rows_per_s": 40000, "span_sample": 16, "settle_s": 0.5}
    (b / "mixes" / "crr-burst-two.json").write_text(json.dumps(mix))
    (b / "metrics" / "verdicts_seen.py").write_text(
        "def read(ctx):\n    return ctx['delta']['verdicts']\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cell = "two-endpoints.crr-burst-two"
    spec["configs"].append({**spec["configs"][0], "name": cfg["name"],
                            "file": "benchmark/configs/two-endpoints.json"})
    spec["workloads"].append({"name": cell, "config": cfg["name"],
                              "traffic": "crr-burst-two", "chips": 1,
                              "why": "added by name"})
    spec["per_layer"].append({"name": "verdicts_seen", "unit": "verdicts",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "batcher",
                              "moves": "verdict_p99_ms",
                              "workloads": [cell]})
    # an end-to-end metric whose reader is already there
    spec["end_to_end"].append({"name": "verdict_p99_ms", "unit": "ms",
                               "better": "lower", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(b / "run.py"), "--workload", cell,
         "--seed", "2147483777", "--seconds", "1.5", "--trace", "1",
         "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 1, p.stderr[-3000:]
    lines = [ln for ln in p.stderr.splitlines()
             if ln.startswith('{"correct"')]
    assert lines, p.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"], result["checks"]
    assert result["metrics"]["verdicts_seen"]["value"] > 0
    guards = json.loads(p.stdout.splitlines()[-1])["guards"]
    # both endpoints served rows, and the rows of the second one were
    # compared with its own policy and conntrack
    assert guards["rows_compared"] > 1000
    after = {p: p.read_bytes() for p in before}
    assert after == before
