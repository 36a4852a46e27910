"""Required-work counts on shapes worked by hand, and the trace reduction
on synthetic intervals and on a small trace recorded on the CPU."""
from __future__ import annotations

import time

import pytest

import tinycells  # noqa: F401  (puts the benchmark on the path)
import trace_reduce
import work

# L=1 layer, D=4, 2 query heads sharing 1 kv head of 2, F=8, V=10
D = {"L": 1, "D": 4, "H": 2, "Hkv": 1, "hd": 2, "F": 8, "V": 10}


def test_block_params_by_hand():
    # wq 4x4 + wk, wv 4x2 each + wo 4x4 + 3 MLP 4x8
    assert work.block_matmul_params(D) == 16 + 16 + 16 + 96


@pytest.mark.parametrize("n,pairs", [(1, 1), (3, 6), (4, 10)])
def test_causal_pairs(n, pairs):
    assert work.causal_pairs(n) == pairs


def test_prefill_counts_real_tokens_and_one_head_row():
    # 2*144*3 matmul + 4*H*hd*6 pairs + one head row 2*4*10
    assert work.prefill_flops(D, 3) == 864 + 96 + 80


def test_decode_counts_the_cache_length():
    assert work.decode_flops(D, 5) == 288 + 4 * 2 * 2 * 5 + 80


def test_train_is_three_forwards_with_every_head_row():
    assert work.train_flops(D, 2, 3) == 3 * 2 * (864 + 240 + 96)


def test_flash_forward_and_training():
    assert work.flash_fwd(D, 1, 3) == (96, 3 * 2 * (2 * 2 + 2 * 1) * 2)
    flops, bytes_ = work.flash_train(D, 1, 3)
    assert flops == 3 * 96
    # forward 72 B, backward Q,K,V,O,dO read and dQ,dK,dV written
    assert bytes_ == 72 + 3 * 2 * (3 * 2 + 2 + 2 + 2) * 2


def test_kvq_bytes_follow_live_lengths():
    flops, bytes_ = work.kvq_decode(D, [3, 5])
    assert flops == 4 * 2 * 2 * 8
    # int8 K and V plus an f32 scale per position, and each slot's bf16
    # query and output
    assert bytes_ == 2 * 1 * 8 * (2 + 4) + 2 * 2 * 2 * 2 * 2


@pytest.mark.parametrize("flops,bytes_,want", [(100, 10, 10.0),
                                               (10, 100, 20.0)])
def test_roofline_takes_the_binding_bound(flops, bytes_, want):
    peaks = {"bf16_flops_per_s": 10.0, "hbm_bytes_per_s": 5.0}
    assert work.roofline_seconds(flops, bytes_, peaks) == want


def test_union_and_gaps():
    iv = [(1.0, 2.0), (1.5, 3.0), (4.0, 5.0)]
    assert trace_reduce.union_seconds(iv) == 3.0
    assert trace_reduce.gaps_between(iv, 0.0, 6.0) == [
        (0.0, 1.0), (3.0, 4.0), (5.0, 6.0)]
    assert trace_reduce._label((3.0, 4.0), [("step", 2.5, 3.2),
                                            ("submit", 3.2, 4.0)]) == "submit"


def test_leaves_drop_enclosing_events():
    loop = (0.0, 10.0, "while", {})
    body = [(1.0, 2.0, "dot", {}), (2.0, 4.0, "kernel", {})]
    after = (11.0, 12.0, "copy", {})
    assert trace_reduce.leaves([after, loop] + body) == body + [after]


def test_reduce_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("step"):
                f(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    r = trace_reduce.reduce(trace_reduce.find_trace(str(tmp_path)),
                            platform="cpu")
    assert 0.06 <= r["window_s"] < 5.0
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert trace_reduce.kernel_seconds(r, [r"dot"]) > 0.0
    assert trace_reduce.kernel_seconds(r, [r"no_such_kernel"]) == 0.0
    # the sleeps between steps are idle, and no annotation covers them
    assert sum(s for s, _ in r["gaps"]) >= 0.05
    b = trace_reduce.breakdown(r)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert {label for label, _ in b["idle_gaps"]} <= {"step", "(none)"}
