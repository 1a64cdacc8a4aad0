"""What decides ``correct``: the controls fail the committed limits, and
a run whose timed path is broken underneath comes out not correct.

The runs here skip the harness's look for a chip and drive the rest of
a run (set-up, window, check) at sizes a CPU test holds.
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator, run as brun, spec
from bench.drivers import scan_calls
from bench.reference import prefix_sum
from bench.tests.test_bench_reference import smoke_conf

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}


def _cell(name, **traffic):
    c = spec.cell(name, spec.benchmark())
    return dataclasses.replace(c, traffic=dict(c.traffic, **traffic))


def _run(cell, seconds=1.0, seed=2**31 + 5):
    r = brun.Run(cell=cell, seed=seed, seconds=seconds, trace=False,
                 peaks=PEAKS)
    return brun.run_cell(r, CPU)


def _scan_cell(name, shape):
    """The cell's driver and configuration at a test size; ``shape`` is a
    single column or a batch of rows."""
    c = _cell(name, shape=list(shape))
    return dataclasses.replace(c, config=dict(c.config,
                                              elements=int(np.prod(shape))))


# -- prefix sums ------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_scan_control_fails_the_limit_and_the_program_passes(seed):
    limit = spec.load_json(f"{spec.BENCH_DIR}/configs/scan-f32-1gib.json")[
        "limits"]["cumsum_rel_err"]
    x = np.asarray(jax.random.uniform(generator.jax_key(seed), (1 << 20,),
                                      jnp.float32, -1, 1))
    y = np.asarray(jax.jit(scan_calls.entry())(jnp.asarray(x)))
    assert prefix_sum.rel_err(x, y) < limit / 3
    assert prefix_sum.rel_err(x, prefix_sum.control_answer(x)) > 3 * limit


def _drop_one_block(cumsum):
    """An answer altered where it is produced: the running sum forgets
    one 2048-element block's total from that block on."""
    def broken(x, **kw):
        y = cumsum(x, **kw)
        lost = jnp.sum(x[..., 2048:4096], axis=-1, keepdims=True)
        return y.at[..., 4096:].add(-lost)
    return broken


def _half_rows_left_out(cumsum):
    """Half of the batch left out: the second half of the rows is not
    scanned."""
    def broken(x, **kw):
        y = cumsum(x, **kw)
        return y.at[x.shape[0] // 2:].set(0.0)
    return broken


@pytest.mark.parametrize("shape,fault", [
    ((1 << 16,), None),
    ((64, 1024), None),
    ((1 << 16,), _drop_one_block),
    ((64, 8192), _drop_one_block),
    ((64, 1024), _half_rows_left_out),
])
def test_scan_run_is_correct_unless_broken(monkeypatch, shape, fault):
    if fault is not None:
        entry = scan_calls.entry()
        monkeypatch.setattr(scan_calls, "entry", lambda: fault(entry))
    res = _run(_scan_cell("scan.column", shape))
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res["checks"])[0] == "cumsum_rel_err"


# -- serving ----------------------------------------------------------

def _deepen(conf, depth_ratio):
    """Scale the residual-writing gains of a cut model by the square root
    of the depth it stands in for."""
    gains = conf["init"]["gains"]
    for leaf in ("wo", "w_down"):
        gains[leaf] *= depth_ratio ** 0.5


def _serve_cell():
    """Head and expert widths of 64, 4 layers, a 4096-token vocabulary,
    float32; the residual-writing gains keep the input embedding's share
    of the residual stream what the full depth gives it."""
    conf = smoke_conf()
    conf["model"].update(num_layers=4, d_model=256, num_heads=4,
                         num_kv_heads=2, head_dim=64, moe_d_ff=64,
                         vocab_size=4096)
    _deepen(conf, 24 / 4)
    conf["check"]["pad_to"] = 64
    c = _cell("granite.chat", rate_per_s=4.0,
              prompt_len={"median": 24, "sigma": 0.8,
                          "round_up_to": [16, 32]},
              output_len={"median": 12, "sigma": 0.5, "min": 4, "max": 24})
    return dataclasses.replace(c, config=conf)


def _alter_tokens(monkeypatch):
    """A token altered where it is produced: every third sampled batch
    takes each row's least likely token."""
    from repro.serve import engine
    orig = engine.Engine._sample
    calls = [0]

    def sample(self, logits):
        calls[0] += 1
        if calls[0] % 3 == 0:
            return jnp.argmin(logits, axis=-1).astype(jnp.int32)
        return orig(self, logits)
    monkeypatch.setattr(engine.Engine, "_sample", sample)


def _stale_state(monkeypatch):
    """A step that returns its state unchanged: decode computes the next
    logits but hands back the cache it was given."""
    from repro.serve import engine
    orig = engine._jit_step

    def jit_step(*a, **kw):
        step = orig(*a, **kw)

        def stale(params, tokens, cache, cache_len):
            logits, _ = step(params, tokens, cache, cache_len)
            return logits, cache
        return stale
    monkeypatch.setattr(engine, "_jit_step", jit_step)


@pytest.mark.parametrize("fault", [None, _alter_tokens, _stale_state])
def test_serve_run_is_correct_unless_broken(monkeypatch, fault):
    if fault is not None:
        fault(monkeypatch)
    cell = _serve_cell()
    res = _run(cell, seconds=2.0)
    assert res["correct"] is (fault is None), res["checks"]
    due = generator.schedule(cell.traffic, 2**31 + 5, 2.0,
                             cell.config["model"]["vocab_size"])
    assert res["attempted"] == len(due) > 0 and res["failed"] == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fails_the_limit(seed):
    """The float8 control at the published widths cut to 4 layers, 8
    experts (top 2) and an 8192-token vocabulary; the residual-writing
    gains keep the input embedding's share of the residual stream what
    24 layers at the configuration's gains give it (``L * gain**2``
    fixed). Its own top token lies further below the float32
    reference's best than the committed limit allows."""
    from bench.drivers import serve_open_loop as drv
    conf = copy.deepcopy(spec.load_json(
        f"{spec.BENCH_DIR}/configs/granite-moe-1b-a400m.json"))
    full = conf["model"]["num_layers"]
    conf["model"].update(num_layers=4, num_experts=8, top_k=2,
                         vocab_size=8192)
    _deepen(conf, full / 4)
    w = drv.make_weights(conf, seed)
    toks = np.random.default_rng(seed).integers(0, 8192, 256).astype(
        np.int32)
    ref = drv.reference(conf)
    g = ref.served_gaps(w, conf["model"], toks[:128], toks[128:], 256,
                        control=True)
    assert g.max() > conf["limits"]["max_logit_gap"]
