"""Roofline infrastructure: HLO collective parser (loop-trip adjusted),
XLA scan-undercount documentation, analytic flop sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.flops import cell_cost, forward_flops_per_tok
from repro.analysis.hlo import collective_bytes, parse_computations, trip_count
from repro.analysis.roofline import analyse_record
from repro.configs.registry import get_config
from repro.configs.shapes import SHAPES


def test_xla_cost_analysis_counts_scan_body_once():
    """Documents WHY the roofline uses analytic FLOPs: XLA counts a while
    body once, so scanned models are undercounted by the trip count."""
    W = jnp.zeros((128, 128), jnp.float32)

    def f_scan(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ W, None), x, None, length=10)
        return y

    def f_unroll(x):
        for _ in range(10):
            x = x @ W
        return x

    def _flops(compiled):
        return compiled.cost_analysis()["flops"]

    x = jnp.zeros((128, 128))
    f1 = _flops(jax.jit(f_scan).lower(x).compile())
    f2 = _flops(jax.jit(f_unroll).lower(x).compile())
    assert f2 == pytest.approx(10 * f1, rel=0.01)


def test_hlo_parser_finds_computations_and_trips():
    hlo = """HloModule test
%body.1 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %c = s32[] constant(1)
}
%cond.1 (p: (s32[], f32[4])) -> pred[] {
  %n = s32[] constant(17)
  ROOT %lt = pred[] compare(%it, %n), direction=LT
}
ENTRY %main (a: f32[4]) -> f32[4] {
  %w = (s32[], f32[4]) while(%t), condition=%cond.1, body=%body.1
}
"""
    comps = parse_computations(hlo)
    assert {"body.1", "cond.1", "main"} <= set(comps)
    assert trip_count(comps["cond.1"]) == 17
    assert comps["main"].while_calls == [("body.1", "cond.1")]


def test_collective_bytes_loop_multiplier():
    hlo = """HloModule test
%body.1 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %ar = f32[64,4]{1,0} all-reduce(%x), channel_id=1, replica_groups=[2,4]<=[8]
}
%cond.1 (p: (s32[], f32[4])) -> pred[] {
  %n = s32[] constant(5)
}
ENTRY %main (a: f32[4]) -> f32[4] {
  %w = (s32[], f32[4]) while(%t), condition=%cond.1, body=%body.1
  %ag = f32[8,4]{1,0} all-gather(%y), dimensions={0}
}
"""
    got = collective_bytes(hlo)
    # all-reduce: 64*4*4B * 2 (ring) * 5 trips = 10240
    assert got["all-reduce"] == pytest.approx(64 * 4 * 4 * 2 * 5)
    # all-gather: result bytes once
    assert got["all-gather"] == pytest.approx(8 * 4 * 4)


@pytest.mark.parametrize("arch", ["granite-8b", "kimi-k2-1t-a32b", "jamba-v0.1-52b"])
def test_analytic_flops_vs_6nd(arch):
    """Analytic forward flops within 2x of the 6ND/2 rule (attention adds
    the quadratic term, MoE counts active experts only)."""
    cfg = get_config(arch)
    shape = SHAPES["train_4k"]
    cc = cell_cost(cfg, shape)
    assert 0.5 <= cc.flops / cc.model_flops <= 2.5
    # decode flops are tiny relative to train (one token vs full batch)
    dec = cell_cost(cfg, SHAPES["decode_32k"])
    assert dec.flops < cc.flops / 100


def test_analyse_record_terms():
    rec = dict(
        status="ok", arch="a", shape="s", mesh="pod16x16", n_devices=256,
        analytic_flops=197e12 * 256,          # exactly 1s of compute
        analytic_hbm_bytes=819e9 * 256 * 0.5,  # 0.5s of memory
        collective_bytes_per_device={"total": 50e9 * 0.25},  # 0.25s
        model_flops=197e12 * 256 * 0.8,
        hlo_flops_raw=1.0,
    )
    row = analyse_record(rec)
    assert row.bottleneck == "compute"
    assert row.compute_s == pytest.approx(1.0)
    assert row.memory_s == pytest.approx(0.5)
    assert row.collective_s == pytest.approx(0.25)
    assert row.mfu_est == pytest.approx(0.8)


def test_skip_records_ignored():
    assert analyse_record({"status": "skip"}) is None
