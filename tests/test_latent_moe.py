"""The latent-attention, routed-expert family (``mxtpu/models/
latent_moe.py``: MLA without a query latent, a sigmoid router with a
selection bias over dropless experts, a shared expert) against its plain
reference (``benchmark/grid/reference/latent_moe.py``: float32,
decompressed attention only, every expert on every token), and through
the paged ``ServeEngine``.

Toy widths that keep the published ratios odd (``CONFIGS["tiny"]``: 4
heads of 24 + 8 with values of 16, a row of 32 + 8 stored 128 wide, 16
experts top-3, one dense and two expert layers), float32 under
conftest's ``highest`` matmul precision. Every comparison is of LOGITS:
where the engine hands back tokens only, each greedy token's reference
logit is held against the reference's maximum at that position
(``argmax_gaps``), which is 0 unless the engine's logits part from the
reference's by more than the gap between the two largest.
"""
import importlib.util
import os
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import telemetry
from mxtpu.models import latent_moe, serving_family
from mxtpu.ops.attention import (gathered_latent_decode_attention,
                                 latent_decode_path,
                                 latent_prefill_attention,
                                 paged_latent_decode_attention)
from mxtpu.parallel import moe
from mxtpu.serve import Request, ServeEngine
from mxtpu.serve.engine import KVHandoff
from mxtpu.serve.gateway import Gateway, GatewayClient

CFG = latent_moe.CONFIGS["tiny"]
MODEL = {"num_hidden_layers": CFG.n_layers,
         "num_attention_heads": CFG.n_heads,
         "qk_nope_head_dim": CFG.qk_nope_head_dim,
         "qk_rope_head_dim": CFG.qk_rope_head_dim,
         "v_head_dim": CFG.v_head_dim, "rms_norm_eps": CFG.norm_eps,
         "rope_theta": CFG.rope_theta,
         "first_k_dense_replace": CFG.first_k_dense,
         "n_routed_experts": CFG.n_routed_experts,
         "num_experts_per_tok": CFG.experts_per_tok,
         "norm_topk_prob": CFG.norm_topk_prob,
         "routed_scaling_factor": CFG.routed_scaling_factor,
         "tie_word_embeddings": False, "vocab_size": CFG.vocab_size}
# float32 against float32 at highest precision: the two differ in the
# order of their sums only (online softmax, absorbed products, sorted
# rows). Logits spread about 1; the largest difference seen is 6e-6
LOGIT_TOL = 1e-4
GAP_TOL = 2 * LOGIT_TOL
ENGINE = dict(max_slots=3, max_len=96, min_bucket=16, page_size=8)
CHUNK = 16
CHUNKED = dict(ENGINE, prefill_chunk=CHUNK)


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "grid", "reference", "latent_moe.py")
    spec = importlib.util.spec_from_file_location("grid_ref_latent_moe",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def _weights(seed):
    """Random weights with the norms' weights moved off their initial
    1, so a layer that dropped one of them would show."""
    params = latent_moe.init_params(CFG, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 50))

    def move(path, a):
        if path[-1].key.endswith("norm"):
            return a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def params():
    return _weights(1)


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in lengths]


def _gaps(params, prompt, tokens, pad_to=96):
    return np.asarray(ref.argmax_gaps(MODEL, params, list(prompt),
                                      list(tokens), pad_to))


def _bank(slots=3, n_pages=40, ps=8):
    kv = latent_moe.init_paged_cache(CFG, slots, n_pages, ps)
    return kv, {m: kv.pop(m) for m in ("lengths", "tokens", "rngs")}


SAMPLE = (jax.random.PRNGKey(3), np.float32(0.0), np.int32(CFG.vocab_size),
          np.float32(1.0))
# one compile of each program for the whole file
FORWARD = jax.jit(lambda p, t: latent_moe.forward(CFG, p, t))
PICKS = jax.jit(lambda p, t: latent_moe.router_picks(CFG, p, t))
PREFILL = jax.jit(partial(latent_moe.prefill_slot_paged, CFG))
PREFILL_CHUNK = jax.jit(partial(latent_moe.prefill_slot_paged_chunk, CFG))
PREFILL_LAST = jax.jit(partial(latent_moe.prefill_slot_paged_last, CFG))
DECODE_LOGITS = jax.jit(partial(latent_moe.decode_logits, CFG))
DROPLESS = jax.jit(moe.moe_ffn_dropless)


# -- the router ----------------------------------------------------------------
def _route(x, w, b, **kw):
    kw = {"top_k": 3, "renorm": True, "scale": 1.0, **kw}
    return moe.route_sigmoid(x, w, b, **kw)


def test_router_bias_changes_the_choice_and_not_the_weight():
    x = jax.random.normal(jax.random.PRNGKey(0), (12, 20))
    w = jax.random.normal(jax.random.PRNGKey(1), (20, 16)) / 4
    idx0, w0 = _route(x, w, jnp.zeros(16), renorm=False)
    bias = jnp.zeros(16).at[5].set(10.0)
    idx1, w1 = _route(x, w, bias, renorm=False)
    s = jax.nn.sigmoid(x @ w)
    assert bool((idx1[:, 0] == 5).all())        # chosen by every token
    assert not bool((idx0 == 5).any(-1).all())  # which s alone does not
    # its weight is s itself, under 1; the bias is not in it
    np.testing.assert_allclose(w1[:, 0], s[:, 5], rtol=1e-6)
    np.testing.assert_allclose(w0, jnp.take_along_axis(s, idx0, -1),
                               rtol=1e-6)


@pytest.mark.parametrize("scale", [1.0, 2.448])
def test_router_renormalises_then_scales(scale):
    x = jax.random.normal(jax.random.PRNGKey(2), (9, 20))
    w = jax.random.normal(jax.random.PRNGKey(3), (20, 16)) / 4
    b = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    idx, plain = _route(x, w, b, renorm=False)
    idx2, wts = _route(x, w, b, scale=scale)
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_allclose(wts.sum(-1), scale, rtol=1e-6)
    np.testing.assert_allclose(
        wts, scale * plain / plain.sum(-1, keepdims=True), rtol=1e-6)
    # the reference's router: the same choice, the same weights
    choice, dense = ref.route(x, w, b, 3, True, scale)
    np.testing.assert_array_equal(jnp.sort(idx, -1), jnp.sort(choice, -1))
    np.testing.assert_allclose(
        jnp.take_along_axis(dense, idx, -1), wts, rtol=1e-5)


def test_router_product_is_float32_whatever_the_activations_are():
    """bf16 activations: the scores are those of the float32 product
    of the same values, not of a bf16 product."""
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 256), jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(6), (256, 16)) / 16
         ).astype(jnp.bfloat16)
    _, got = _route(x, w, jnp.zeros(16), top_k=16, renorm=False)
    want = jnp.sort(jax.nn.sigmoid(
        x.astype(jnp.float32) @ w.astype(jnp.float32)), -1)[:, ::-1]
    np.testing.assert_allclose(got, want, atol=1e-6)
    low = jnp.sort(jax.nn.sigmoid((x @ w).astype(jnp.float32)), -1)[:, ::-1]
    assert float(jnp.abs(low - want).max()) > 1e-4


# -- the dispatch ----------------------------------------------------------------
def _every_expert(bank, x, idx, wts, E):
    """``moe_ffn_dense``'s arithmetic with a router's picks: every
    token through every expert, the unchosen weighted zero."""
    dense = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].set(wts)
    h = jax.nn.silu(jnp.einsum("td,edh->teh", x, bank["w_gate"])) \
        * jnp.einsum("td,edh->teh", x, bank["w_up"])
    return jnp.einsum("ted,te->td",
                      jnp.einsum("teh,ehd->ted", h, bank["w_down"]), dense)


def _skewed(T, E=16, K=3, seed=0):
    """A router under which most tokens pick experts 0 and 1, and
    experts 8.. are picked by nobody."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = partial(jax.random.normal, dtype=jnp.float32)
    x = normal(ks[0], (T, 20))
    w = normal(ks[1], (20, E)) / 4
    b = jnp.zeros(E).at[:2].set(5.0).at[8:].set(-5.0)
    bank = {"w_gate": normal(ks[2], (E, 20, 12)) / 4,
            "w_up": normal(ks[3], (E, 20, 12)) / 4,
            "w_down": normal(ks[4], (E, 12, 20)) / 4}
    idx, wts = moe.route_sigmoid(x, w, b, top_k=K, scale=2.448)
    return x, bank, idx, wts


@pytest.mark.parametrize("T", [40, 1])
def test_dropless_dispatch_equals_every_expert_on_every_token(T):
    x, bank, idx, wts = _skewed(T)
    got, sizes = DROPLESS(bank, x, idx, wts)
    np.testing.assert_allclose(got, _every_expert(bank, x, idx, wts, 16),
                               atol=1e-5)
    host = np.bincount(np.asarray(idx).reshape(-1), minlength=16)
    np.testing.assert_array_equal(sizes, host)
    if T > 1:
        assert host[:2].sum() >= 1.9 * T and host[8:].sum() == 0


@pytest.mark.parametrize("m,tm", [(48, 16), (8, 8)])
def test_grouped_matmul_kernel_equals_ragged_dot(m, tm):
    """The TPU's grouped product (``megablox.gmm``, here in Pallas'
    interpret mode) against ``lax.ragged_dot``, which stands in for it
    off the TPU: a stacked bank of 3 x 5 groups of which one layer's
    have rows, some of those none, and rows past the groups' total."""
    ks = jax.random.split(jax.random.PRNGKey(m), 2)
    lhs = jax.random.normal(ks[0], (m, 20), jnp.float32)
    rhs = jax.random.normal(ks[1], (15, 20, 12), jnp.float32)
    sizes = np.zeros(15, np.int32)
    sizes[5:10] = (m // 2, 0, m // 8, 0, m // 4)
    got = jax.jit(partial(moe.grouped_matmul_kernel, tm=tm,
                          interpret=True))(lhs, rhs, jnp.asarray(sizes))
    want = jax.lax.ragged_dot(lhs, rhs, jnp.asarray(sizes))
    live = int(sizes.sum())
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5)
    assert 0 < live < m


def test_dropless_dispatch_on_a_layer_of_a_stacked_bank():
    """The whole stack's bank with the layer's index gives what the
    layer's own slab gives; a token that is not valid is routed nowhere
    and comes out zero."""
    x, bank, idx, wts = _skewed(40)
    stack = {n: jnp.stack([a * 0 + 7.0, a, a * 0 - 3.0])
             for n, a in bank.items()}
    valid = jnp.arange(40) % 5 != 0
    got, sizes = DROPLESS(stack, x, idx, wts, layer=jnp.int32(1),
                          valid=valid)
    want = jnp.where(valid[:, None],
                     _every_expert(bank, x, idx, wts, 16), 0.0)
    np.testing.assert_allclose(got, want, atol=1e-5)
    host = np.bincount(np.asarray(idx)[np.asarray(valid)].reshape(-1),
                       minlength=16)
    np.testing.assert_array_equal(sizes, host)


# -- attention -------------------------------------------------------------------
def test_absorbed_decode_equals_decompressed_prefill_attention():
    """One layer's attention on the same rows in both forms: the last
    query of a run through ``latent_prefill_attention`` (keys and
    values rebuilt per head) against ``paged_latent_decode_attention``
    over pages holding those rows, with the query carried into the
    latent space and the output carried out of it."""
    H, nope, rope, dv, R, s, ps = 4, 24, 8, 16, 32, 21, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    normal = partial(jax.random.normal, dtype=jnp.float32)
    q_nope = normal(ks[0], (1, H, s, nope))
    q_rope = normal(ks[1], (1, H, s, rope))
    rows = jnp.pad(normal(ks[2], (1, 1, 24, R + rope)),
                   ((0, 0), (0, 0), (0, 0), (0, 128 - R - rope)))
    wkvb = normal(ks[3], (R, H, nope + dv)) / 4
    scale = 1.0 / np.sqrt(nope + rope)
    want = jax.jit(partial(latent_prefill_attention, layer=0, q_offset=0,
                           scale=scale, kv_block=8))(q_nope, q_rope, rows,
                                                     wkvb)
    decode = jax.jit(partial(paged_latent_decode_attention, layer=0,
                             value_dim=R, scale=scale))
    # the rows as pages 3, 1, 2 of a pool of 5; page 0 is scratch
    pool = jnp.zeros((1, 5, ps, 128), jnp.float32).at[
        0, jnp.asarray([3, 1, 2])].set(
        rows[0, 0].reshape(3, ps, 128))
    table = jnp.asarray([[3, 1, 2, 0]], jnp.int32)
    for t in (20, 7, 0):
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, :, t],
                           wkvb[..., :nope])
        q = jnp.concatenate([q_lat, q_rope[:, :, t]], -1)[:, :, None]
        o_lat = decode(q, pool, table, jnp.asarray([t + 1]))
        got = jnp.einsum("bhsr,rhv->bhsv", o_lat, wkvb[..., nope:])
        np.testing.assert_allclose(got[:, :, 0], want[:, :, t], atol=1e-5)
    assert latent_decode_path(q.shape, pool.shape, pool.dtype) == "gathered"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_latent_pages_kernel_matches_the_gathered_path(dtype):
    """The Pallas kernel (interpreted here) walks each slot's live pages
    of a latent pool, once, as keys and values: the gathered path's
    numbers up to the order of summation; zeros for a slot of length 0;
    nothing of a page past the length (they hold NaN); idle slots read
    scratch page 0. Two pages to a block and one to a chunk, so a slot
    takes one block, several, or a block half read."""
    from mxtpu.ops.paged_attention import paged_latent_pages
    S, H, per_slot, ps, row, L = 5, 4, 6, 16, 128, 2
    rng = np.random.default_rng(31)
    nan_page = 1 + S * per_slot
    pool = rng.standard_normal((L, nan_page + 1, ps, row)).astype(np.float32)
    pool[:, 0], pool[:, nan_page] = 0.0, np.nan
    pool = jnp.asarray(pool, dtype)
    q = jnp.asarray(rng.standard_normal((S, H, 1, row)), dtype)
    lengths = np.asarray([0, 1, 16, 37, 96], np.int32)
    table = (1 + rng.permutation(S * per_slot)).astype(
        np.int32).reshape(S, per_slot)
    clean = table.copy()
    for s_, n in enumerate(lengths):             # NaN past every length
        table[s_, -(-int(n) // ps):] = nan_page
        clean[s_, -(-int(n) // ps):] = 0
    table[0], clean[0] = 0, 0                    # an idle slot's row
    kernel = jax.jit(partial(paged_latent_pages, layer=jnp.int32(1),
                             scale=0.1, block_pages=2, chunk_pages=1,
                             interpret=True))
    got = np.asarray(kernel(q, pool, jnp.asarray(table),
                            jnp.asarray(lengths)), np.float32)
    want = np.asarray(gathered_latent_decode_attention(
        q, pool, jnp.asarray(clean), jnp.asarray(lengths), layer=1,
        value_dim=row, scale=0.1), np.float32)
    assert np.isfinite(got).all() and not got[0].any()
    tol = 1e-5 if dtype == jnp.float32 else 4 * 2.0 ** -8 * np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=tol)


def test_prefill_attention_at_an_offset_reads_the_rows_before_it():
    """A run of queries at positions 16.. over a row store equals the
    same positions of one run from 0."""
    H, nope, rope, dv, R = 4, 24, 8, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    normal = partial(jax.random.normal, dtype=jnp.float32)
    q_nope = normal(ks[0], (1, H, 32, nope))
    q_rope = normal(ks[1], (1, H, 32, rope))
    rows = normal(ks[2], (2, 1, 48, R + rope))
    wkvb = normal(ks[3], (R, H, nope + dv)) / 4
    kw = dict(layer=1, scale=0.2, kv_block=16)
    attend = jax.jit(partial(latent_prefill_attention, **kw))
    whole = attend(q_nope, q_rope, rows, wkvb, q_offset=jnp.int32(0))
    later = attend(q_nope[:, :, 16:], q_rope[:, :, 16:], rows, wkvb,
                   q_offset=jnp.int32(16))
    np.testing.assert_allclose(later, whole[:, :, 16:], atol=1e-5)


# -- the model against the reference -------------------------------------------
@pytest.mark.parametrize("seed", [1, 2])
def test_forward_matches_reference_logits(seed):
    params = _weights(seed)
    toks = _prompts(seed, [40])[0]
    got = FORWARD(params, toks[None])[0]
    picks = []
    want = ref.logits(MODEL, params, jnp.asarray(toks), picks=picks)
    assert float(jnp.abs(want).max()) > 2.0      # logits spread about 1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)
    mine = PICKS(params, toks[None])
    np.testing.assert_array_equal(np.sort(mine, -1),
                                  np.sort(np.stack(picks), -1))


def test_each_layer_alone_matches_the_reference_layer(params):
    """``layer_streams`` hands out the stream entering every layer and
    leaving the last: the reference's layer applied to each gives the
    next (what the benchmark's check holds a layer's own arithmetic to,
    where a whole forward would carry one router flip into every later
    layer)."""
    toks = _prompts(4, [40])[0]
    streams = jax.jit(lambda p, t: latent_moe.layer_streams(CFG, p, t))(
        params, toks[None])[:, 0]
    assert streams.shape == (CFG.n_layers + 1, 40, CFG.dim)
    np.testing.assert_allclose(
        streams[0], params["tok_embed"][toks], atol=1e-6)
    for index in range(CFG.n_layers):
        want = ref.layer(MODEL, params, index, streams[index])
        assert float(jnp.abs(want - streams[index]).max()) > 0.05
        np.testing.assert_allclose(streams[index + 1], want, atol=1e-5)


def _prefill_whole(params, toks, true_len, row, slot=1):
    padded = np.zeros((1, 96), np.int32)
    padded[0, :true_len] = toks[:true_len]
    return PREFILL(params, padded, np.int32(true_len), np.int32(0), row,
                   np.int32(slot), *_bank(), *SAMPLE)


def _row(true_len, ps=8, cap=96, first=1):
    n = -(-(true_len + 4) // ps)
    row = np.zeros(cap // ps, np.int32)
    row[:n] = np.arange(first, first + n)
    return row


@pytest.mark.parametrize("true_len", [70, 64, 33, 16, 5])
def test_prefill_in_chunks_seats_what_the_whole_prefill_seats(params,
                                                              true_len):
    """A prompt prefilled 16 tokens at a time through the stage (which
    starts out holding another prompt's leavings) seats the rows the
    one-program prefill seats and samples the same first token: a
    chunk attends to the rows the chunks before it left."""
    toks = _prompts(true_len, [true_len])[0]
    row = _row(true_len)
    tok0, kv0, sv0 = _prefill_whole(params, toks, true_len, row)
    stage = jax.tree_util.tree_map(
        lambda a: a + 3, latent_moe.init_prefill_stage(CFG, 96, CHUNK))
    done = 0
    while true_len - done > CHUNK:
        stage = PREFILL_CHUNK(params, toks[None, done:done + CHUNK],
                              np.int32(done), stage)
        done += CHUNK
    tail = np.zeros((1, CHUNK), np.int32)
    tail[0, :true_len - done] = toks[done:]
    tok1, kv1, sv1 = PREFILL_LAST(
        params, tail, np.int32(done), np.int32(true_len - done), stage,
        row, np.int32(1), *_bank(), *SAMPLE)
    assert int(tok0[0]) == int(tok1[0])
    assert int(sv1["lengths"][1]) == true_len == int(sv0["lengths"][1])
    live = row[:-(-true_len // 8)]
    got = np.asarray(kv1["latent"][:, live]).reshape(CFG.n_layers, -1, 128)
    want = np.asarray(kv0["latent"][:, live]).reshape(CFG.n_layers, -1, 128)
    np.testing.assert_allclose(got[:, :true_len], want[:, :true_len],
                               atol=1e-5)
    assert np.abs(want[:, :true_len, :CFG.row_dim]).max() > 0.5
    assert not want[:, :true_len, CFG.row_dim:].any()   # the padding


@pytest.mark.parametrize("true_len", [34, 16])
def test_decode_through_the_pages_matches_one_full_forward(params, true_len):
    """Prefill then six absorbed decode steps over the pages, teacher-
    forced: every step's logits equal the full forward's (decompressed,
    no cache) at that position, and the reference's; the device-side
    counts equal counts made on the host from the router's picks."""
    toks = _prompts(7, [40])[0]          # FORWARD's and PICKS' shape
    row = _row(true_len + 6)
    table = np.zeros((3, 12), np.int32)
    table[1] = row
    _, kv, sv = _prefill_whole(params, toks, true_len, row)
    full = FORWARD(params, toks[None])[0]
    picks = np.asarray(PICKS(params, toks[None]))
    want = ref.logits(MODEL, params, jnp.asarray(toks))
    active = np.asarray([False, True, False])
    for t in range(true_len, true_len + 6):
        sv = dict(sv, tokens=sv["tokens"].at[1].set(int(toks[t])),
                  lengths=sv["lengths"].at[1].set(t))
        logits, pool, counts = DECODE_LOGITS(params, kv, sv, active, table)
        kv = {"latent": pool}
        np.testing.assert_allclose(logits[1], full[t], atol=LOGIT_TOL)
        np.testing.assert_allclose(logits[1], want[t], atol=LOGIT_TOL)
        # one token, top-3, two expert layers: six assignments, six
        # experts touched (a layer's picks are distinct), load 1 each
        np.testing.assert_array_equal(counts, [6, 6, 2])
        assert len(set(picks[0, t])) == 3


def test_step_counts_equal_counts_made_on_the_host(params):
    """Three slots decode one token each (one of them not active): the
    counts behind the sampled tokens are those of the active slots'
    picks, recomputed here from ``router_picks`` on each slot's own
    sequence."""
    prompts = _prompts(21, (20, 33, 9))
    table = np.zeros((3, 12), np.int32)
    kv, sv = _bank()
    firsts = []
    for slot, p in enumerate(prompts):
        table[slot] = _row(len(p) + 2, first=1 + 12 * slot)
        padded = np.zeros((1, 96), np.int32)
        padded[0, :len(p)] = p
        tok, kv, sv = PREFILL(params, padded, np.int32(len(p)), np.int32(0),
                              table[slot], np.int32(slot), kv, sv, *SAMPLE)
        firsts.append(int(tok[0]))
    active = np.asarray([True, False, True])
    out, _, _ = jax.jit(partial(latent_moe.decode_slots_paged, CFG))(
        params, kv, sv, active, table, np.zeros(3, np.float32),
        np.full(3, CFG.vocab_size, np.int32), np.ones(3, np.float32))
    out = np.asarray(out)
    assert out.shape == (3 + len(latent_moe.STEP_COUNTS),)
    loads = np.zeros((CFG.n_moe_layers, CFG.n_routed_experts), int)
    for slot in (0, 2):
        seq = np.zeros((1, 40), np.int32)      # FORWARD's and PICKS' shape
        n = len(prompts[slot]) + 1
        seq[0, :n] = np.append(prompts[slot], firsts[slot])
        last = np.asarray(PICKS(params, seq))[:, n - 1]
        for layer, chosen in enumerate(last):
            loads[layer, chosen] += 1
    assert out[3] == loads.sum() == 2 * 3 * CFG.n_moe_layers
    assert out[4] == (loads > 0).sum()
    assert out[5] == int(np.float32(loads.max(1).sum()) * 1e6
                         / np.float32(loads.sum()))


# -- through the engine ------------------------------------------------------------
@pytest.fixture(scope="module")
def served(params):
    """Two engines, one prefilling whole prompts and one in chunks of 16
    (with decode steps of the requests already running in between),
    each given the same six greedy requests over three slots (so slots
    are reused and hold requests of different ages in one step) and
    three sampled ones. {name: (engine, greedy prompts, their tokens,
    the sampled requests' tokens, the step counters' change)}."""
    greedy, sampled = _prompts(0, (30, 41, 17, 33, 64, 9)), \
        _prompts(11, (45, 20, 66))
    reg, out = telemetry.registry(), {}
    for name, kw in (("whole", ENGINE), ("chunked", CHUNKED)):
        eng = ServeEngine(CFG, params, **kw)
        before = [reg.value(c["name"]) for c in latent_moe.STEP_COUNTS[:2]]
        g = [eng.submit(Request(prompt=p, max_new_tokens=12,
                                temperature=0.0)) for p in greedy]
        s = [eng.submit(Request(prompt=p, max_new_tokens=8, seed=i,
                                temperature=0.7, top_p=0.9))
             for i, p in enumerate(sampled)]
        got = eng.run()
        out[name] = (eng, greedy, [got[r] for r in g], [got[r] for r in s],
                     [reg.value(c["name"]) - b for c, b in
                      zip(latent_moe.STEP_COUNTS[:2], before)])
    return out


def test_family_surface_and_state_bytes(served):
    assert serving_family(CFG) is latent_moe
    assert {"prefix_cache", "speculate_k", "int8_pages", "submit_prefilled",
            "mesh"} == set(latent_moe.SERVE_UNSUPPORTED)
    eng = served["whole"][0]
    kv = eng.kv_cache_stats()
    # a token's row a layer is 32 + 8 values, stored as one lane tile
    assert (CFG.row_dim, CFG.row_stored) == (40, 128)
    assert latent_moe.LatentMoEConfig().row_stored == 640
    tok = CFG.n_layers * CFG.row_stored * 4
    assert kv["reserved_bytes"] == eng.n_pages * 8 * tok
    assert kv["state_bytes_per_slot"] == 0
    assert kv["decode_attention"] == "gathered"
    line = next(ln for ln in telemetry.prometheus().splitlines()
                if ln.startswith("mxtpu_serve_state_bytes{engine="
                                 f'"{eng.engine_id}",kind="latent_pages"}}'))
    assert float(line.split()[-1]) == eng.n_pages * 8 * tok


@pytest.mark.parametrize("name", ["whole", "chunked"])
def test_engine_run_matches_reference(params, served, name):
    """Prefill + 12 decode steps through ``ServeEngine.run()``: every
    emitted token is the reference's argmax at its position."""
    eng, prompts, tokens, _, (assigned, touched) = served[name]
    for p, toks in zip(prompts, tokens):
        assert len(toks) == 12
        assert _gaps(params, p, toks).max() <= GAP_TOL
    if name == "chunked":
        assert eng.n_buckets == 2 and eng.compile_count == 3
    else:
        assert eng.compile_count == 1 + eng.n_buckets
    # every decode-step token was assigned top-3 experts in two layers:
    # 11 steps a greedy request and 7 a sampled one (the first token is
    # the prefill's), and at most one more each, dispatched before its
    # last token was read back
    assert assigned % 6 == 0
    assert 6 * (66 + 21) <= assigned <= 6 * (72 + 24)
    assert 0 < touched <= assigned
    assert "mxtpu_serve_moe_load_max_share_bucket" in telemetry.prometheus()


def test_chunked_and_whole_prefill_sample_the_same_stream(served):
    for whole, chunked in zip(served["whole"][3], served["chunked"][3]):
        assert len(whole) == 8
        np.testing.assert_array_equal(whole, chunked)


def test_gateway_matches_reference(params):
    """The same through ``Gateway.start_http``: streamed tokens of four
    concurrent requests, prompts prefilled in chunks."""
    gw = Gateway(lambda: ServeEngine(CFG, params, **CHUNKED),
                 n_replicas=1, queue_max=16)
    prompts = _prompts(5, (27, 35, 52, 11))
    results = {}
    try:
        port = gw.start_http(port=0)

        def client(i):
            results[i] = GatewayClient("127.0.0.1", port).generate(
                prompts[i], 8, seed=i, temperature=0.0)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        _, prom = GatewayClient("127.0.0.1", port).get_text("/metrics")
    finally:
        gw.close()
    assert 'kind="latent_pages"' in prom
    assert "mxtpu_serve_moe_assignments_total" in prom
    for i, p in enumerate(prompts):
        assert results[i]["status"] == 200, results[i]
        assert len(results[i]["tokens"]) == 8
        assert _gaps(params, p, results[i]["tokens"]).max() <= GAP_TOL


@pytest.mark.parametrize("option,word", [
    ({"prefix_cache": True}, "rows already in the pool"),
    ({"speculate_k": 2}, "verify step"),
    ({"int8_pages": True}, "quantised")])
def test_engine_refuses_what_it_cannot_do(params, option, word):
    with pytest.raises(ValueError, match="latent_moe family.*" + word):
        ServeEngine(CFG, params, **{**ENGINE, **option})


def test_engine_refuses_a_prefilled_handoff_and_a_mesh(params):
    eng = ServeEngine(CFG, params, **ENGINE)
    assert eng.prefix_cache_enabled is False     # the default, not asked
    z = np.zeros((CFG.n_layers, 1, 16, CFG.row_dim), np.float32)
    handoff = KVHandoff(k=z, v=z, true_len=9, token=1,
                        rng=np.zeros(2, np.uint32))
    with pytest.raises(ValueError, match="submit_prefilled.*latent rows"):
        eng.submit_prefilled(handoff, Request(
            prompt=np.arange(9), max_new_tokens=2))
    from mxtpu.parallel import mesh as pmesh
    with pytest.raises(ValueError, match="mesh.*expert bank"):
        ServeEngine(CFG, params, mesh=pmesh.create_mesh(dp=-1), **ENGINE)


@pytest.mark.parametrize("kw,word", [
    (dict(prefill_chunk=40), "divides the slot's capacity"),
    (dict(max_len=2048, prefill_chunk=1024 + 512), "divides the slot's"),
])
def test_engine_refuses_a_chunk_that_does_not_fit(params, kw, word):
    with pytest.raises(ValueError, match=word):
        ServeEngine(CFG, params, **{**ENGINE, **kw})
