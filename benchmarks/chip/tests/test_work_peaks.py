"""Operation and byte counts against values worked by hand, and the peak
table's refusals."""

import json
import os

import pytest

import peaks
import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_smollm_counts():
    w = work.Work(load("smollm-360m"), "w8a8", "bf16")
    # per layer: q 960*960 + k,v 2*960*320 + o 960*960 + mlp 3*960*2560
    #   = 921600 + 614400 + 921600 + 7372800 = 9830400; 32 layers
    assert w.lin_params == 314_572_800
    assert w.head_params == 47_185_920          # 960 * 49152, tied: bf16
    assert w.score_ops == 122_880               # 32 * 2 * (15*64 + 15*64)
    assert w.kv_elems == 20_480                 # 32 * 2 * 5 * 64
    ops = w.token_ops(100)
    assert ops.int8 == 629_145_600              # 2 * 314572800
    assert ops.bf16 == 106_659_840              # 2 * 47185920 + 122880*100
    p = w.prompt_ops(512)
    assert p.int8 == 322_122_547_200            # 629145600 * 512
    # head once + 122880 * 512*513/2
    assert p.bf16 == 94_371_840 + 16_137_584_640
    # int8 linears + bf16 tied head + 8000 keys * 40960 B of bf16 K and V
    assert w.weight_bytes(16) == 408_944_640
    assert w.decode_bytes(16, 8000) == 736_624_640
    assert w.experts_hit(16) == 0


def test_deepseek_stage_counts():
    w = work.Work(load("deepseek-v2-lite-pp4"), "w8a8", "bf16")
    # attention per layer: q 2048*3072 + kv_a 2048*576 + kv_b 512*4096
    #   + o 2048*2048 = 13762560, 7 layers = 96337920; dense MLP
    #   3*2048*10944 = 67239936; shared experts 6 * 3*2048*2816 = 103809024
    assert w.lin_params == 267_386_880
    assert w.head_params == 209_715_200         # 2048 * 102400, int8
    assert w.expert_params_each == 8_650_752    # 3 * 2048 * 1408
    assert w.score_ops == 71_680                # 7 * 2 * (16*192 + 16*128)
    assert w.kv_elems == 4_032                  # 7 * (512 + 64)
    ops = w.token_ops(100)
    assert ops.int8 == 954_204_160              # 2 * (267386880 + 209715200)
    # 2 * 6 layers * (6 * 8650752 + 2048*64 router) + 71680 * 100
    assert ops.bf16 == 624_427_008 + 7_168_000
    # 16 tokens, top-6 of 64: 64 * (1 - (58/64)^16) = 50.752...
    assert w.experts_hit(16) == pytest.approx(50.752062, rel=1e-6)
    # int8 linears and head, f32 routers (6 * 131072 * 4 B), bf16 experts
    assert w.weight_bytes(16) == pytest.approx(
        477_102_080 + 3_145_728 + 6 * 50.752062 * 17_301_504, rel=1e-6)
    assert w.decode_bytes(16, 1000) == pytest.approx(
        w.weight_bytes(16) + 1000 * 8_064)


def test_peaks_table():
    v5e = peaks.lookup("tpu", "TPU v5 lite")
    assert v5e == {"bf16_flops": 197e12, "int8_ops": 393e12,
                   "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    assert work.Ops(393e12, 197e12).seconds(v5e) == pytest.approx(2.0)
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("tpu", "TPU v4")
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("cpu", "cpu")
    assert peaks.lookup("cpu", "cpu", allow_cpu=True) is None


def test_run_refuses_the_cpu(capsys):
    import run
    with pytest.raises(SystemExit) as e:
        run.check_device(1, allow_cpu=False)
    assert e.value.code != 0
    assert capsys.readouterr().out == ""
