"""Pinned autotune winners: every checkout hands the program the same
blocks, and the program reads them where the benchmark writes them."""

import json
import os

import pytest

from harness import device, spec
from repro.kernels import autotune
from repro.kernels import paged_attn


def _pins(tmp_path, monkeypatch, entries):
    pins = tmp_path / "autotune"
    pins.mkdir()
    (pins / "TPU_v9.json").write_text(json.dumps(
        {"device_kind": "TPU v9", "backend": "tpu", "entries": entries}))
    monkeypatch.setattr(device, "PINS", pins)
    monkeypatch.delenv(device.AUTOTUNE_ENV, raising=False)


def test_pins_written_over_the_programs_own_entries(tmp_path, monkeypatch):
    key = "paged_attn|128|1|bfloat16|False|False|acdc"
    _pins(tmp_path, monkeypatch, {key: 1032})
    cache = tmp_path / "cache" / "autotune.json"
    cache.parent.mkdir()
    cache.write_text(json.dumps({"backend": "tpu", "entries": {
        key: 520, "fwd|1024|1|bfloat16|False|False|acdc": 128}}))
    assert device.pin_autotune("TPU v9", cache) == {key: 1032}
    assert json.loads(cache.read_text()) == {"backend": "tpu", "entries": {
        key: 1032, "fwd|1024|1|bfloat16|False|False|acdc": 128}}
    assert autotune._cache_path() == str(cache)


def test_no_pins_for_an_unknown_kind(tmp_path, monkeypatch):
    _pins(tmp_path, monkeypatch, {})
    cache = tmp_path / "autotune.json"
    assert device.pin_autotune("TPU v1", cache) is None
    assert not cache.exists()
    assert device.AUTOTUNE_ENV not in os.environ


@pytest.mark.parametrize("pins", sorted(device.PINS.glob("*.json")),
                         ids=lambda p: p.stem)
def test_committed_pins_run_as_pinned_in_every_serving_cell(pins):
    """Each pinned key is the program's, and each cell's decode call site
    runs the pinned block unchanged (``clamp_block`` keeps it)."""
    blob = json.loads(pins.read_text())
    assert blob["backend"] == "tpu"
    assert pins.stem == blob["device_kind"].replace(" ", "_")
    bench = json.loads((device.BENCH.parent / "BENCHMARK.json").read_text())
    for key_s, enc in blob["entries"].items():
        key = autotune._key_from_str(key_s)
        assert autotune._key_str(key) == key_s
        direction, dh, t, dtype = key[:4]
        assert direction == "paged_attn"
        blk = paged_attn.decode_block(enc)
        for w in bench["workloads"]:
            cell = spec.load_cell(w["name"])
            m = cell.config["model"]
            assert m["head_dim"] == dh
            assert paged_attn.clamp_block(
                blk, hkv=m["n_kv_heads"], dh=dh,
                group=m["n_heads"] // m["n_kv_heads"],
                t=t, bs=cell.config["serve"]["block_size"],
                itemsize=2 if dtype == "bfloat16" else 4) == blk
