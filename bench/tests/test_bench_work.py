"""Each kernel's operations and bytes against a count made by hand."""
import sys
import types
from pathlib import Path

import numpy as np

from bench import roofline
from bench.work import moe_gmm, paged_attention, quant_matmul

PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def test_moe_gmm_swiglu_and_down_by_hand():
    # 3 rows, 2 experts touched, d 256, f 128, 2-bit codes, group 128
    flops, nbytes = moe_gmm.swiglu(3, 2, 256, 128, 2, 128)
    assert flops == 2 * 2 * 3 * 256 * 128 == 393216
    codes = 256 * 128 * 2 / 8            # 8192 B per matrix
    scales = 2 * 2 * 128 * 4             # 2 groups x 128 cols x (scale, zero) f32
    assert nbytes == 2 * 2 * (codes + scales) + 2 * 3 * (256 + 128) == 43264
    flops, nbytes = moe_gmm.down(3, 2, 256, 128, 3, 128)
    assert flops == 2 * 3 * 128 * 256
    assert nbytes == 2 * (128 * 256 * 3 / 8 + 2 * 1 * 256 * 4) + 2 * 3 * 384


def test_paged_attention_counts_actual_lengths():
    flops, nbytes = paged_attention.call([5, 7], 4, 2, 64)
    assert flops == 4 * 12 * 4 * 64 == 12288
    assert nbytes == 2 * 2 * 12 * 2 * 64 + 2 * 2 * 2 * 4 * 64 == 8192


def test_quant_matmul_by_hand():
    flops, nbytes = quant_matmul.call(8, 256, 512, 4, 128)
    assert flops == 2 * 8 * 256 * 512
    assert nbytes == 256 * 512 / 2 + 2 * 2 * 512 * 4 + 2 * 8 * (256 + 512)


def test_bound_is_the_larger_of_compute_and_memory():
    assert roofline.bound(2e12, 1e9, PEAK) == (2.0, "compute")
    assert roofline.bound(1e9, 1e11, PEAK) == (1.0, "memory")


def _ctx(**kw):
    model = {"d_model": 256, "d_ff_expert": 128, "num_heads": 4, "num_kv_heads": 2,
             "head_dim": 64, "num_layers": 1, "top_k": 2, "num_shared_experts": 1,
             "num_experts": 4, "vocab_size": 100}
    base = dict(model=model, peak=PEAK, group=128, attn_bits=4,
                buckets=[(2, 0, 2), (3, 2, 2)], step_counts=[], decode_lengths=[],
                trace={"kernel_s": {}})
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_moe_gmm_calls_rebuilt_from_counts():
    counts = np.array([[2, 0, 1, 1]])  # one layer: 2 rows in bucket 0, 2 in bucket 1
    ctx = _ctx(step_counts=[counts], trace={"kernel_s": {"moe_gmm": 1.0}})
    want = sum(roofline.bound(*f(rows, ex, 256, 128, bits, 128), PEAK)[0]
               for rows, ex, bits in ((2, 1, 2), (2, 2, 3))
               for f in (moe_gmm.swiglu, moe_gmm.down))
    acc = roofline.least_time(moe_gmm.calls(ctx), PEAK)
    assert np.isclose(acc["s"], want)
    assert np.isclose(roofline.share("moe_gmm", ctx), 100 * want)


def test_share_is_silent_without_the_kernel():
    ctx = _ctx(decode_lengths=[[3, 4]])
    assert roofline.share("paged_attention", ctx) is None


def test_a_kernel_added_as_a_file_is_found_and_read(tmp_path, monkeypatch):
    """A new ``bench/work/<kernel>.py`` is all the trace reducer and the
    roofline share need: no file that is there already changes."""
    from bench import trace, work

    (tmp_path / "toy_kernel.py").write_text(
        "import re\n"
        "PATTERN = re.compile(r'^toy_kernel')\n"
        "def calls(ctx):\n"
        "    yield 2e9, 1e6\n"
        "    yield 1e6, 3e8\n")
    monkeypatch.setattr(work, "__path__", [*work.__path__, str(tmp_path)])
    work.kernels.cache_clear()
    try:
        assert trace.classify("%toy_kernel_pallas.3 = bf16[8] custom-call()") == "toy_kernel"
        ctx = _ctx(trace={"kernel_s": {"toy_kernel": 0.01}})
        # bounds 2e9 / 1e12 = 2 ms (compute) and 3e8 / 1e11 = 3 ms (memory)
        assert np.isclose(roofline.share("toy_kernel", ctx), 50.0)
    finally:
        monkeypatch.undo()
        work.kernels.cache_clear()
        sys.modules.pop("bench.work.toy_kernel", None)


def test_every_kernel_has_a_pattern_and_its_roofline_reader():
    from bench import work

    bench = Path(__file__).resolve().parents[1]
    assert set(work.kernels()) == {"moe_gmm", "paged_attention", "quant_matmul"}
    for name, mod in work.kernels().items():
        assert mod.PATTERN.search(name) and callable(mod.calls)
        assert (bench / "metrics" / f"{name}_roofline.py").is_file()
