"""counts.py against values worked by hand at the published widths."""
import json
import pathlib

import smoke
import counts

CONF = pathlib.Path(smoke.HERE) / "configs"
QWEN = json.loads((CONF / "qwen2_0_5b.json").read_text())["model"]
MAMBA = json.loads((CONF / "mamba2_780m.json").read_text())["model"]


def test_qwen2_0_5b():
    # per layer: 896*(14+2*2)*64 + 14*64*896 + 3*896*4864 = 14,909,440
    assert counts.matmul_params(QWEN) == 24 * 14_909_440 == 357_826_560
    assert counts.token_flops(QWEN, 0) == 715_653_120
    # attention: 4 * 24 layers * 14 heads * 64 * ctx
    assert counts.token_flops(QWEN, 1000) == 715_653_120 + 86_016_000
    # 512 prompt tokens from position 0: contexts 1..512 sum to 131,328
    assert counts.prefill_flops(QWEN, 0, 512) == 512 * 715_653_120 + 86_016 * 131_328
    assert counts.prefill_flops(QWEN, 512, 512) == 0
    assert counts.unembed_flops(QWEN) == 2 * 896 * 151_936
    # K and V of 2 heads x 64 in bf16 over 24 layers: 12,288 B a position
    assert counts.paged_decode_kernel(QWEN, 1000) == (86_016_000, 12_288_000)


def test_mamba2_780m():
    # per layer: 1536*(2*48*64 + 2*128 + 48) + 48*64*1536 = 14,622,720
    assert counts.matmul_params(MAMBA) == 48 * 14_622_720 == 701_890_560
    # conv 2*4*(3072+256) + state 4*48*64*128 per layer, 48 layers
    assert counts.token_flops(MAMBA, 0) == 1_403_781_120 + 48 * 1_599_488
    assert counts.token_flops(MAMBA, 5000) == counts.token_flops(MAMBA, 0)
    # 300 tokens, chunk 256: triangle 256*257/2 + 44*45/2 = 33,886
    f, b = counts.ssd_scan_kernel(MAMBA, 300, 256)
    assert f == 48 * 48 * (2 * 192 * 33_886 + 4 * 128 * 64 * 300)
    assert b == 48 * 48 * 300 * ((64 + 256) * 2 + 8)
