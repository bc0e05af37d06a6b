"""The benchmark's FLOP counts, against numbers worked by hand."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "chipbench"))

import flops  # noqa: E402


def model(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())["model"]


def test_bert_large_train_flops():
    m = model("bert-large")
    # layer: attention 4·1024² = 4,194,304; MLP 2·1024·4096 = 8,388,608;
    # two layer norms 4·1024 = 4,096 → 12,587,008; ×24 + final norm 2,048
    assert flops.non_embedding_params(m) == 302_090_240
    assert flops.head_params(m) == 30522 * 1024 == 31_254_528
    # 6·(302,090,240 + 31,254,528) = 2,000,068,608; attention
    # 12·24·16·64·256.5 (mean causal context at 512) = 75,644,928
    assert flops.train_flops_per_token(m, 512) == pytest.approx(2_075_713_536)


def test_danube_stage_train_flops():
    m = model("h2o-danube-1.8b-pp4")
    # layer: q,o 2·2560·2560 = 13,107,200; k,v 2·2560·640 = 3,276,800;
    # SwiGLU 3·2560·6912 = 53,084,160; two RMSNorms 5,120 → 69,473,280
    assert flops.layer_params(m) == 69_473_280
    assert flops.non_embedding_params(m) == 6 * 69_473_280 + 2560
    # 6·(416,842,240 + 81,920,000) = 2,992,573,440; attention
    # 12·6·32·80·2048.5 (window 4096 = sequence) = 377,579,520
    assert flops.train_flops_per_token(m, 4096) == pytest.approx(3_370_152_960)


def test_mean_context_window():
    # positions 0..7 with window 4 see 1,2,3,4,4,4,4,4 keys
    assert flops.mean_context(8, 4) == pytest.approx(26 / 8)
    assert flops.mean_context(512, None) == pytest.approx(256.5)
