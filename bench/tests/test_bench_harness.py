"""BENCHMARK.json against the contract, the generator, the counts, and
the harness's refusal to run without a chip."""

import os
import re
import shutil
import subprocess
import sys

import numpy as np

from bench import flops, generator, spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|expan)"
                   r"|_dim$|_rank$|experts_per_tok|top_k|d_model|d_ff")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert TEXT.match(word) and not word.startswith("/")
        assert ".." not in word
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_names_units_and_entry_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and TEXT.match(c["source"])
        assert TEXT.match(c["why"]) and len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and TEXT.match(w["why"])
        names.append(w["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert TEXT.match(m["layer"])
    assert len(names) == len(set(names))


def test_every_cell_finds_its_files_by_name():
    used = set()
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"], BENCH)
        driver = cell.driver()
        for fn in ("setup", "measure", "facts", "end_to_end", "check"):
            assert callable(getattr(driver, fn))
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2
        used.add(w["config"])
    files = [c["file"] for c in BENCH["configs"]]
    assert used == {c["name"] for c in BENCH["configs"]}
    assert len(files) == len(set(files))
    for f in files:
        assert f.startswith("bench/") and os.path.isfile(
            os.path.join(spec.ROOT, f))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_each_layer_metric_has_a_reader_and_its_cells_report_what_it_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert callable(spec.reader(m["name"]).read)
        assert m["moves"] in e2e
        for w in m.get("workloads", [x["name"] for x in BENCH["workloads"]]):
            assert spec.reports(e2e[m["moves"]], w), (m["name"], w)
        layers.setdefault(m["layer"], set()).add(m["name"])
    assert "device" in layers


def test_run_seconds_fits_a_full_check_with_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_schedule_repeats_for_a_seed_and_every_seed_gets_the_same_work():
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                      "chat-steady.json"))
    a = generator.schedule(mix, 2**31 + 11, 51, 49155)
    b = generator.schedule(mix, 2**31 + 11, 51, 49155)
    c = generator.schedule(mix, 5, 51, 49155)
    work = lambda rs: [(r.arrival_s, r.prompt.size, r.max_new_tokens)
                       for r in rs]
    assert work(a) == work(b) == work(c)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert {r.prompt.size for r in a} <= set(mix["prompt_len"]["round_up_to"])
    assert all(8 <= r.max_new_tokens <= 512 for r in a)
    assert all(0 < r.arrival_s < 51 for r in a)
    # a shorter window replays the start of the same draw
    assert work(generator.schedule(mix, 5, 20, 49155)) == \
        [w for w in work(a) if w[0] < 20]


def test_schedule_is_poisson_with_lognormal_lengths():
    """Over a long draw the gaps are exponential (mean 1/rate, as many
    below the mean as an exponential puts there, clusters included) and
    the lengths follow the stated lognormals."""
    import statistics
    mix = {"rate_per_s": 2.0, "schedule_seed": 3,
           "prompt_len": {"median": 384, "sigma": 0.8,
                          "round_up_to": [256, 1024]},
           "output_len": {"median": 48, "sigma": 0.8, "min": 8, "max": 512}}
    reqs = generator.schedule(mix, 1, 5000, 100)
    assert abs(len(reqs) - 10_000) < 400
    gaps = np.diff([r.arrival_s for r in reqs])
    assert abs(gaps.mean() - 0.5) < 0.02
    assert abs(np.mean(gaps < 0.5) - (1 - np.exp(-1))) < 0.02
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05
    nd = statistics.NormalDist(np.log(384), 0.8)
    share = np.mean([r.prompt.size == 256 for r in reqs])
    assert abs(share - nd.cdf(np.log(256))) < 0.02
    out = np.array([r.max_new_tokens for r in reqs])
    assert abs(np.median(out) - 48) <= 2
    assert out.min() >= 8 and out.max() <= 512
    # the schedule is the mix's: another schedule_seed, another draw
    other = generator.schedule(dict(mix, schedule_seed=4), 1, 50, 100)
    assert [r.arrival_s for r in other] != [r.arrival_s for r in reqs[
        :len(other)]]


def test_serve_mfu_counts_every_token_of_the_window():
    import types
    m = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                    "granite-moe-1b-a400m.json"))["model"]
    facts = {"model": m, "window_s": 50.0,
             "window_prefill_lengths": [512, 2048],
             "window_decode_keys": [513, 514, 2049]}
    ctx = types.SimpleNamespace(facts=facts,
                                peaks={"flops_bf16": 197e12})
    work = (flops.prefill_flops(m, 512) + flops.prefill_flops(m, 2048)
            + sum(flops.decode_flops(m, k) for k in (513, 514, 2049)))
    got = spec.reader("serve.mfu").read(ctx)
    assert abs(got - 100 * work / 50.0 / 197e12) < 1e-12
    assert spec.reader("serve.mfu").read(types.SimpleNamespace(
        facts=dict(facts, window_prefill_lengths=[], window_decode_keys=[]),
        peaks=ctx.peaks)) is None


def test_jax_key_keeps_every_bit_of_the_seed():
    import jax
    k = [np.asarray(jax.random.key_data(generator.jax_key(s)))
         for s in (7, 7 + 2**32, 2**31 + 7)]
    assert not np.array_equal(k[0], k[1])
    assert not np.array_equal(k[0], k[2])
    assert np.array_equal(k[0], np.asarray(jax.random.key_data(
        generator.jax_key(7))))


def test_scan_bytes_count_one_read_and_one_write():
    assert flops.scan_bytes((2**28,), 4) == 2 * 2**28 * 4
    assert flops.scan_bytes((4096, 65536), 4) == 2**31


def test_granite_flops_from_the_published_sizes():
    m = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                    "granite-moe-1b-a400m.json"))["model"]
    per_layer = (1024 * 16 * 64 * 2 + 2 * 1024 * 8 * 64 + 1024 * 32
                 + 8 * 3 * 1024 * 512)
    assert flops.moe_matmul_params(m) == 24 * per_layer == 378_273_792
    head = 2 * 1024 * 49155
    assert flops.decode_flops(m, 1) == 2 * 378_273_792 + 4 * 24 * 1024 \
        + head
    # a causal prefill attends 1 + 2 + ... + S keys in all
    assert flops.prefill_flops(m, 3) == 3 * 2 * 378_273_792 \
        + 4 * 24 * 1024 * 6 + head


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "scan.column",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_run_exits_nonzero_without_a_chip():
    p = _run(spec.ROOT, {})
    assert p.returncode == 3, p.stderr
    assert p.stdout == ""
    assert "no accelerator" in p.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {})
    assert p.returncode != 0
    assert p.stdout == ""
    assert "No module named 'repro'" in p.stderr
