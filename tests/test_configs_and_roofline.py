"""Registry completeness, cell builders, HLO collective parser."""

import pytest

from repro.configs import get_arch, list_archs
from repro.launch.roofline import (RooflineTerms, parse_collective_bytes,
                                   peaks)

ASSIGNED = [
    "deepseek-moe-16b", "llama4-maverick-400b-a17b", "command-r-35b",
    "command-r-plus-104b", "qwen3-32b",
    "nequip", "pna", "gat-cora", "dimenet", "xdeepfm",
]


def test_all_assigned_archs_registered():
    archs = list_archs()
    for a in ASSIGNED:
        assert a in archs, f"missing assigned arch {a}"
    assert "sssp" in archs  # the paper's own


def test_cell_matrix_counts():
    """36 runnable assigned cells (4 long_500k skips documented) + 2
    SSSP cells."""
    runnable = sum(len(get_arch(a).shapes) for a in ASSIGNED)
    assert runnable == 36
    skipped = sum(1 for a in ASSIGNED
                  if get_arch(a).kind == "lm"
                  and "long_500k" not in get_arch(a).shapes)
    assert skipped == 4
    assert len(get_arch("sssp").shapes) == 2


def test_exact_brief_numbers():
    c = get_arch("deepseek-moe-16b").full
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (28, 2048, 16, 16, 1408, 102400)
    assert (c.moe.n_experts, c.moe.top_k, c.moe.n_shared) == (64, 6, 2)
    c = get_arch("llama4-maverick-400b-a17b").full
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (48, 5120, 40, 8, 8192, 202048)
    assert (c.moe.n_experts, c.moe.top_k) == (128, 1)
    c = get_arch("command-r-35b").full
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (40, 8192, 64, 8, 22528, 256000)
    c = get_arch("command-r-plus-104b").full
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (64, 12288, 96, 8, 33792, 256000)
    c = get_arch("qwen3-32b").full
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (64, 5120, 64, 8, 25600, 151936)
    assert c.qk_norm
    c = get_arch("xdeepfm").full
    assert c.n_fields == 39 and c.embed_dim == 10
    assert c.cin_layers == (200, 200, 200) and c.mlp_dims == (400, 400)
    c = get_arch("nequip").full
    assert (c.n_layers, c.mult, c.l_max, c.n_rbf, c.cutoff) == \
        (5, 32, 2, 8, 5.0)
    c = get_arch("pna").full
    assert (c.n_layers, c.d_hidden) == (4, 75)
    c = get_arch("gat-cora").full
    assert (c.n_layers, c.d_hidden, c.n_heads, c.in_dim) == (2, 8, 8, 1433)
    c = get_arch("dimenet").full
    assert (c.n_blocks, c.d_hidden, c.n_bilinear, c.n_spherical,
            c.n_radial) == (6, 128, 8, 7, 6)


HLO_SAMPLE = """
  %ag = bf16[2048,1024]{1,0} all-gather(%p0), replica_groups={...}
  %ar.1 = f32[128]{0} all-reduce-start(f32[128]{0} %x), to_apply=%add
  %rs = (f32[64,32]{1,0}, f32[64,32]{1,0}) reduce-scatter(%a, %b)
  %a2a = bf16[16,512]{1,0} all-to-all(%y), dimensions={0}
  %cp = u32[8]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %dot = f32[128,128]{1,0} dot(%l, %r)
"""


def test_collective_parser():
    got = parse_collective_bytes(HLO_SAMPLE)
    assert got["all-gather"] == 2048 * 1024 * 2
    assert got["all-reduce"] == 128 * 4
    assert got["reduce-scatter"] == 2 * 64 * 32 * 4
    assert got["all-to-all"] == 16 * 512 * 2
    assert got["collective-permute"] == 8 * 4
    assert got["count"] == 5
    assert got["total"] == sum(
        got[k] for k in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute"))


def test_roofline_terms_math():
    t = RooflineTerms(flops=197e12, bytes_accessed=819e9,
                      collective_bytes=50e9, n_chips=256,
                      model_flops=197e12 * 256 * 0.5)
    assert abs(t.t_compute - 1.0) < 1e-9
    assert abs(t.t_memory - 1.0) < 1e-9
    assert abs(t.t_collective - 1.0) < 1e-9
    assert abs(t.roofline_fraction - 0.5) < 1e-9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_peaks_refuse_unknown_device_kind(kind):
    assert peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks(kind)


def test_lm_smoke_cells_buildable():
    """Cell builders construct for every assigned (arch, shape) without
    touching a mesh (lower() itself is the dry-run's job)."""
    for a in ASSIGNED:
        spec = get_arch(a)
        for s in spec.shapes:
            cell = spec.build_cell(spec.full, s)
            assert cell.model_flops > 0
            assert cell.kind in ("train", "prefill", "decode", "serve",
                                 "retrieval", "sssp")
