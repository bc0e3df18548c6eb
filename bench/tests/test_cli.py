"""The command refuses to run without a chip or without the program, and
the traffic is a function of the seed."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np

from bench import traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def bench_run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(2**31 + 12345), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = bench_run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = bench_run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def items(mix, seed, n=40):
    g = traffic.Generator(traffic.load_mix(mix), seed, 1000, 32)
    if g.mix["loop"] == "closed":
        out = g.first_requests()
        out += [g.next_request(i % 32) for i in range(n)]
    else:
        out = g.schedule(30.0)
    return [(i.uid, i.client, round(i.due, 9), i.max_new, tuple(i.prompt))
            for i in out]


def test_traffic_is_a_function_of_the_seed():
    for mix in sorted(p.stem for p in traffic.TRAFFIC_DIR.glob("*.json")):
        seed = 2**31 + 7
        assert items(mix, seed) == items(mix, seed)
        assert items(mix, seed) != items(mix, seed + 1)


def test_every_seed_gets_the_same_sizes():
    mix = traffic.load_mix("decode")
    a = traffic.Generator(mix, 1, 1000, 32)
    b = traffic.Generator(mix, 2**33 + 5, 1000, 32)
    assert sorted(a.output_lens) == sorted(b.output_lens)
    assert sorted(a.prompt_lens) == sorted(b.prompt_lens)
    assert not np.array_equal(a.output_lens, b.output_lens)
    ra = sorted(i.max_new for i in a.first_requests())
    rb = sorted(i.max_new for i in b.first_requests())
    assert ra == rb
    spec = mix["output"]
    lens = traffic.length_quantiles(spec)
    assert lens.min() >= spec["min"] and lens.max() <= spec["max"]
    assert abs(np.median(lens) - spec["median"]) <= 1


def test_every_name_in_the_benchmark_has_its_file():
    bench = ROOT / "bench"
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert (bench / "traffic" / f"{w['traffic']}.json").is_file()
        assert (bench / "limits" / f"{w['name']}.json").is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (bench / "metrics" / f"{m['name']}.py").is_file()


def test_config_shapes_are_what_the_program_runs():
    from bench import harness

    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        c = harness.program_bundle(cell).config
        s = cell.config["shape"]
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.hd,
                c.d_ff, c.vocab, c.rope_style, c.rope_theta, c.norm_eps,
                c.window, c.max_seq, c.param_dtype) == (
            s["n_layers"], s["d_model"], s["n_heads"], s["n_kv_heads"],
            s["head_dim"], s["d_ff"], s["vocab"], s["rope_style"],
            s["rope_theta"], s["norm_eps"], s["window"], s["max_seq"],
            s["dtype"])
