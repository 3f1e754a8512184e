"""The in-place paged decode attention (``kernels/paged_attn.py`` plus
the combine in ``models/common.py::paged_decode_attention``), in
interpret mode, against the gather path it replaces on the chip
(``paged_cache_view`` + ``decode_attention`` / ``decode_step``): the
attention output and per-key mass of one layer, and a whole round's
logits, mass and arena after the scatter; and the rule that chooses
between the two."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels.paged import gather_pages
from repro.models import common as C
from repro.models.registry import build_model

CS, PAGES_A_ROW, POOL = 16, 16, 48

# name -> (positions of the 4 rows, window, n_sinks); a negative
# position marks a pad row: page row all 0 (scratch page), position 0
CASES = {
    "ragged": ([100, 37, 5, 200], 0, 0),
    "page_edges": ([32, 47, 16, 15], 0, 0),
    "pad_rows_single_token": ([60, 1, -1, -1], 0, 0),
    "window_sinks": ([100, 37, 5, 200], 40, 4),
}
GROUPS = {"gqa5": (10, 2), "mha": (2, 2)}       # (H, KV)


def _pool(case, KV, L, seed=0):
    """Arenas with distinct pages per row, page tables and positions."""
    rng = np.random.RandomState(seed)
    raw, window, n_sinks = CASES[case]
    pos = np.maximum(np.asarray(raw, np.int32), 0)
    pt = np.zeros((len(raw), PAGES_A_ROW), np.int32)
    free = iter(rng.permutation(np.arange(1, POOL)))
    for b, p in enumerate(raw):
        if p >= 0:                        # the new token's page included
            pt[b, :p // CS + 1] = [next(free) for _ in range(p // CS + 1)]
    shape = (L, POOL, CS, KV, 128)
    arenas = {n: jnp.asarray(rng.randn(*shape), jnp.bfloat16)
              for n in ("k16", "v16")}
    return arenas, jnp.asarray(pt), jnp.asarray(pos), window, n_sinks, rng


@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("case", CASES)
def test_layer_attention_matches_gather_path(case, group):
    H, KV = GROUPS[group]
    arenas, pt, pos, window, n_sinks, rng = _pool(case, KV, L=2)
    B = pt.shape[0]
    q = jnp.asarray(2 * rng.randn(B, 1, H, 128), jnp.bfloat16)
    k_new = jnp.asarray(2 * rng.randn(B, 1, KV, 128), jnp.bfloat16)
    v_new = jnp.asarray(rng.randn(B, 1, KV, 128), jnp.bfloat16)
    layer = 1
    out, mass = jax.jit(functools.partial(
        C.paged_decode_attention, window=window, n_sinks=n_sinks,
        want_density=True))(q, k_new, v_new, arenas["k16"], arenas["v16"],
                            layer, pt, pos)
    # the gather path: dense view, the new token written at pos, attend
    k_c = C.ring_update(gather_pages(arenas["k16"], pt)[layer], k_new, pos)
    v_c = C.ring_update(gather_pages(arenas["v16"], pt)[layer], v_new, pos)
    ref_out, ref_mass = C.decode_attention(q, k_c, v_c, pos + 1,
                                           window=window, n_sinks=n_sinks,
                                           want_density=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(mass), np.asarray(ref_mass),
                               rtol=1e-4, atol=1e-6)
    # no mass beyond the new token, which gets its share at pos
    k_pos = np.arange(mass.shape[1])
    assert np.all(np.asarray(mass)[k_pos[None] > np.asarray(pos)[:, None]]
                  == 0)
    np.testing.assert_allclose(np.asarray(mass).sum(1), 1.0, rtol=1e-5)


@functools.lru_cache(maxsize=None)
def _model(H, KV):
    cfg = dataclasses.replace(reduced(get_config("qwen2.5-14b")),
                              n_layers=2, n_heads=H, n_kv_heads=KV,
                              head_dim=128)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _round(model, params, args, window, n_sinks, in_place, monkeypatch):
    monkeypatch.setattr(C, "on_tpu", lambda: in_place)
    fn = jax.jit(functools.partial(
        model.decode_paged, want_density=True, unroll=model.cfg.n_layers,
        window=window, n_sinks=n_sinks))
    return fn(params, *args)


@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("case", CASES)
def test_decode_round_matches_gather_path(case, group, monkeypatch):
    H, KV = GROUPS[group]
    cfg, model, params = _model(H, KV)
    arenas, pt, pos, window, n_sinks, rng = _pool(case, KV, cfg.n_layers)
    toks = jnp.asarray(rng.randint(0, cfg.vocab, (pt.shape[0], 1)),
                       jnp.int32)
    args = (toks, arenas, pt, None, None, pos)
    a0, logits0, mass0 = _round(model, params, args, window, n_sinks,
                                False, monkeypatch)
    a1, logits1, mass1 = _round(model, params, args, window, n_sinks,
                                True, monkeypatch)
    np.testing.assert_allclose(np.asarray(logits1), np.asarray(logits0),
                               rtol=0, atol=1e-2)
    # looser than one layer's mass: deeper layers see inputs rounded
    # through a different softmax
    np.testing.assert_allclose(np.asarray(mass1), np.asarray(mass0),
                               rtol=5e-3, atol=1e-5)
    # the scatter wrote exactly the new tokens: layer 0's bit for bit
    # (same inputs), deeper layers' within a bf16 step of the gather
    # path's (their inputs went through a differently rounded softmax)
    rows = np.arange(pt.shape[0])
    pages, offs = np.asarray(pt)[rows, np.asarray(pos) // CS], np.asarray(
        pos) % CS
    for n in ("k16", "v16"):
        new0 = np.asarray(a0[n][:, pages, offs], np.float32)
        new1 = np.asarray(a1[n][:, pages, offs], np.float32)
        np.testing.assert_array_equal(new1[0], new0[0])
        np.testing.assert_allclose(new1, new0, rtol=2e-2, atol=2e-2)
        keep = np.ones(a0[n].shape[1:3], bool)
        keep[pages, offs] = False
        np.testing.assert_array_equal(np.asarray(a1[n])[:, keep],
                                      np.asarray(arenas[n])[:, keep])


@pytest.mark.parametrize("hd,quant,tpu,expect", [
    (128, False, True, True),
    (64, False, True, False),
    (128, True, True, False),
    (128, False, False, False),
], ids=["hd128", "hd64_gathers", "pt8_gathers", "off_tpu_gathers"])
def test_rule_chooses_the_path(hd, quant, tpu, expect, monkeypatch):
    monkeypatch.setattr(C, "on_tpu", lambda: tpu)
    arenas = {"k16": jax.ShapeDtypeStruct((2, 5, CS, 2, hd), jnp.bfloat16)}
    pt8 = jnp.zeros((1, 4), jnp.int32) if quant else None
    assert C.paged_decode_in_place(arenas, pt8) is expect


def test_latent_arena_takes_the_gather_path(monkeypatch):
    """The leaf-generic round: with the rule's backend check steered to
    the chip, a dense ``k``/``v`` arena still takes the kernel while
    MLA's latent ``ckv``/``kpe`` arenas (no ``k16``) take the gather
    path without error, write the round's new latents into their pages,
    and return the routed layers' expert ids; the answer and the round
    are those of the CPU's gather path.  The new latents the decode step
    hands the round (``DecodeOut.new_kv``) are the rows it writes into
    its cache at ``pos``."""
    monkeypatch.setattr(C, "on_tpu", lambda: True)
    dense = {"k16": jax.ShapeDtypeStruct((2, 5, CS, 2, 128), jnp.bfloat16)}
    assert C.paged_decode_in_place(dense, None)
    cfg = reduced(get_config("deepseek-v2-lite-16b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    m, L = cfg.mla, cfg.n_layers
    rng = np.random.RandomState(0)
    arenas = {"ckv16": jnp.asarray(rng.randn(L, POOL, CS, m.kv_lora_rank),
                                   jnp.bfloat16),
              "kpe16": jnp.asarray(rng.randn(L, POOL, CS,
                                             m.qk_rope_head_dim),
                                   jnp.bfloat16)}
    assert not C.paged_decode_in_place(arenas, None)
    pos = np.asarray([40, 17], np.int32)
    pt = np.zeros((2, PAGES_A_ROW), np.int32)
    pt[0, :3], pt[1, :2] = [5, 9, 2], [7, 11]
    toks = jnp.asarray([[3], [4]], jnp.int32)
    args = (toks, arenas, jnp.asarray(pt), None, None, jnp.asarray(pos))
    a1, logits1, mass1, ids1 = _round(model, params, args, 0, 0, True,
                                      monkeypatch)
    a0, logits0, mass0, ids0 = _round(model, params, args, 0, 0, False,
                                      monkeypatch)
    np.testing.assert_array_equal(np.asarray(logits1), np.asarray(logits0))
    np.testing.assert_array_equal(np.asarray(ids1), np.asarray(ids0))
    n_moe = L - cfg.moe.first_dense
    assert ids1.shape == (n_moe, 2, cfg.moe.top_k)
    assert np.all((0 <= np.asarray(ids1))
                  & (np.asarray(ids1) < cfg.moe.n_experts))
    pages, offs = pt[[0, 1], pos // CS], pos % CS
    for n in ("ckv16", "kpe16"):
        keep = np.ones(arenas[n].shape[1:3], bool)
        keep[pages, offs] = False
        np.testing.assert_array_equal(np.asarray(a1[n])[:, keep],
                                      np.asarray(arenas[n])[:, keep])
        new = np.asarray(a1[n])[:, pages, offs]
        assert not np.array_equal(new, np.asarray(arenas[n])[:, pages, offs])
        np.testing.assert_array_equal(new, np.asarray(a0[n])[:, pages, offs])
    view = C.paged_cache_view(arenas, ("ckv", "kpe"), jnp.asarray(pt), None,
                              None, jnp.asarray(pos))
    out = model.decode_step(params, toks, view)
    for n in ("ckv", "kpe"):
        written = np.asarray(out.cache[n])[:, [0, 1], pos]
        np.testing.assert_array_equal(np.asarray(out.new_kv[n]), written)
        np.testing.assert_array_equal(np.asarray(a0[n + "16"])[:, pages,
                                                                offs],
                                      written)
