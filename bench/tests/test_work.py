"""The work-count functions, against counts made by hand at StarCoder2-15B
widths (d_model 6144, 48 query and 4 KV heads of 128, d_ff 24576, vocab
49152, group 128), 4 layers."""
import json
from pathlib import Path

from harness import spec
from harness import weights as W
from harness.serving import StepWork

CONFIG = Path(__file__).resolve().parent / "data" / "starcoder2-15b-4L.json"

# one layer's quantized linears, Ci x Co:
#   wq 6144x6144, wk 6144x512, wv 6144x512, wo 6144x6144,
#   up 6144x24576, down 24576x6144
PARAMS = 383_778_816            # sum of Ci*Co over them
IO = 99_328                     # sum of Ci+Co over them


def dims():
    return W.dims(json.loads(CONFIG.read_text()))


def reader(name):
    return spec.load_metric_reader(name)


def test_linear_shapes():
    d = dims()
    assert sum(ci * co for _, ci, co in W.linear_shapes(d)) == PARAMS
    assert sum(ci + co for _, ci, co in W.linear_shapes(d)) == IO


def test_w4a16_bytes_of_one_decode_step():
    m = reader("w4a16_matmul_roofline")
    step = StepWork(decode_ctx=[100] * 64)
    calls = m.calls(dims(), [step])
    assert len(calls) == 4 * 6
    # per layer: int4 codes 383778816 / 2 = 191889408 bytes; a bf16 scale
    # and zero per group of 128 rows and column, 4 * 383778816 / 128 =
    # 11993088; bf16 activations in and out, 2 * 64 * 99328 = 12713984
    assert sum(b for _, b in calls) == 4 * (191_889_408 + 11_993_088
                                            + 12_713_984)
    assert sum(f for f, _ in calls) == 2 * 64 * PARAMS * 4


def test_w4a16_counts_prefill_rows_that_carry_requests():
    m = reader("w4a16_matmul_roofline")
    step = StepWork(decode_ctx=[5, 6],
                    prefill=[(512, [0], [300], [True]),
                             (64, [16, 32], [40, 50], [False, True])])
    assert m.launch_rows(step) == [2, 300, 90]
    f, b = m.call_work(300, 6144, 24576, 128)
    assert f == 2 * 300 * 6144 * 24576
    assert b == 6144 * 24576 // 2 + 4 * 48 * 24576 + 2 * 300 * (6144 + 24576)


def test_paged_decode_work():
    m = reader("paged_gqa_decode_roofline")
    f, b = m.row_work(1000, dims())
    assert f == 4 * 1000 * 48 * 128 == 24_576_000
    # K and V of 1000 tokens x 4 heads x 128, and q and out of 48 x 128,
    # all bf16
    assert b == 2 * (2 * 1000 * 4 + 2 * 48) * 128 == 2_072_576
    calls = m.calls(dims(), [StepWork(decode_ctx=[1000, 1000]),
                             StepWork()])
    assert calls == [(2 * f, 2 * b)] * 4


def test_paged_prefill_work():
    m = reader("paged_gqa_prefill_roofline")
    f, b = m.row_work(512, 512, dims())
    # causal pairs: 512 * 512 behind the chunk + 512 * 513 / 2 inside it
    assert f == 4 * 48 * 128 * (262_144 + 131_328) == 9_669_967_872
    assert b == 2 * 128 * (2 * 1024 * 4 + 2 * 512 * 48) == 14_680_064


def test_step_flops():
    m = reader("step_mfu")
    d = dims()
    per_tok = 2 * 4 * PARAMS            # 3,070,230,528
    head = 2 * 6144 * 49152             # 603,979,776
    attn = 4 * 4 * 48 * 128             # per key, all layers
    st = StepWork(decode_ctx=[10, 20])
    assert m.step_flops(d, st) == 2 * (per_tok + head) + attn * 30
    st = StepWork(prefill=[(16, [0], [3], [False])])
    assert m.step_flops(d, st) == 3 * per_tok + attn * (1 + 2 + 3)
    st = StepWork(prefill=[(16, [4], [2], [True])])
    assert m.step_flops(d, st) == 2 * per_tok + attn * (5 + 6) + head
