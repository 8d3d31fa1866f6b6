"""The rule that sizes the held experts' sorted-assignment buffer
(parallel/ep.py ``_row_prefixes``): at a decode step's assignments the one
buffer of ``_row_buffer``, at a chunk's a short prefix of the sorted order
beside it, chosen on the device by how many assignments fell on held
experts. Whatever the routing, ``held_experts_ffn`` equals a plain loop
over each token's held picks, and ``carried`` says which buffer ran.

The three shares are the cells' (Granite-4.0-H-Small 36 of 72 top-10,
GLM-5.2 16 of 256 top-8, LongCat-Flash-Chat 16 of 768 top-12 with 256
identity outputs) at their chunk's or largest bucket's tokens; the widths
are toys, which the rule never reads.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.parallel import ep

D, F = 16, 8
SHARES = {
    "granite": (2048, ep.ExpertShareConfig(
        n_outputs=72, top_k=10, d_ff=F, held_count=36, renormalise=True)),
    "glm": (2048, ep.ExpertShareConfig(
        n_outputs=256, top_k=8, d_ff=F, held_count=16, scoring="sigmoid",
        renormalise=True, scale=2.5)),
    "longcat": (512, ep.ExpertShareConfig(
        n_outputs=768, n_identity=256, top_k=12, d_ff=F, held_count=16,
        scale=6.0)),
}
# rows of a decode step's buffer: lanes x top-k of the three cells
STEPS = {"granite": 64 * 10, "glm": 32 * 8, "longcat": 128 * 12}


def _steered(name, live):
    """(h, params, cfg) whose router sends exactly ``live`` of the N x k
    assignments to held experts: three kinds of token, told apart by their
    first three features, which the router alone reads - every pick on the
    first k held experts, one pick on held expert k + 2 (so k and k + 1,
    and every one after, get NO row), no pick on a held expert."""
    n, cfg = SHARES[name]
    k = cfg.top_k
    full, one = divmod(live, k)
    assert full + one <= n and k + 2 < cfg.held_count
    params = ep.init_expert_share(jax.random.key(7), D, cfg)
    away = cfg.held_count + np.arange(k)           # absent experts
    router = np.zeros((D, cfg.n_outputs), np.float32)
    router[0, :k] = 8.0
    router[1, away[:-1]] = 8.0
    router[1, k + 2] = 8.0
    router[2, away] = 8.0
    kind = np.repeat([0, 1, 2], [full, one, n - full - one])
    # the kinds interleaved, so that the sort has work to do
    kind = kind[np.random.default_rng(live).permutation(n)]
    h = np.array(jax.random.normal(jax.random.key(8), (n, D)))
    h[:, :3] = np.eye(3, dtype=np.float32)[kind]
    return jnp.asarray(h), {**params, "router": jnp.asarray(router)}, cfg


def _plain(h, pick, weight, params, cfg):
    """Token by token, pick by pick: weight x expert(h) over the held."""
    h, pick, weight = (np.asarray(a, np.float64) for a in (h, pick, weight))
    w1, w3, w2 = (np.asarray(params[n], np.float64)
                  for n in ("we1", "we3", "we2"))
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        for e, w in zip(pick[t].astype(int) - cfg.held_offset, weight[t]):
            if 0 <= e < cfg.held_count:
                gate = h[t] @ w1[e]
                out[t] += w * ((gate / (1 + np.exp(-gate)) * (h[t] @ w3[e]))
                               @ w2[e])
    return out


def _cases():
    for name, (n, cfg) in SHARES.items():
        short, whole = ep._row_prefixes(n * cfg.top_k, cfg)
        for case, live, rows in (
                ("inside", short - cfg.top_k - 1, short),
                ("exactly_full", short, short),
                ("one_past", short + 1, whole),
                ("every_assignment_held", n * cfg.top_k, whole)):
            yield pytest.param(name, live, rows, id=f"{name}-{case}")


@pytest.mark.parametrize("name, live, rows", _cases())
def test_any_routing_gives_the_plain_loops_sum(name, live, rows):
    h, params, cfg = _steered(name, live)
    pick, weight = ep.dropless_route(h, params, cfg)
    assert int(ep._on_held(pick, cfg)[1].sum()) == live
    got = jax.jit(lambda h, p: ep.held_experts_ffn(
        h, *ep.dropless_route(h, p, cfg), p, cfg))(h, params)
    want = _plain(h, pick, weight, params, cfg)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())
    _y, counts = jax.jit(lambda h, p: ep.dropless_moe(h, p, cfg))(h, params)
    assert int(counts["carried"]) == rows
    assert int(counts["held"].sum()) == live
    # held experts k, k + 1 and those after k + 2 got no row
    assert int(counts["touched"]) == cfg.top_k + (live % cfg.top_k > 0)


@pytest.mark.parametrize("name", SHARES)
def test_rows_the_grouped_matmul_leaves_unwritten_reach_no_token(
        name, monkeypatch):
    """A prefix exactly full but for one row, that row poisoned (the
    grouped matmul writes nothing past the last group): every assignment
    past the prefix is clamped onto it, at weight 0, and the mask keeps
    ``0 x NaN`` from the tokens."""
    n, cfg = SHARES[name]
    short, _whole = ep._row_prefixes(n * cfg.top_k, cfg)
    h, params, cfg = _steered(name, short - 1)
    real = jax.lax.ragged_dot

    def unwritten(rows, stack, sizes):
        out = real(rows, stack, sizes)
        return jnp.where((jnp.arange(out.shape[0]) < sizes.sum())[:, None],
                         out, jnp.nan)
    monkeypatch.setattr(ep.lax, "ragged_dot", unwritten)
    pick, weight = ep.dropless_route(h, params, cfg)
    got = ep.held_experts_ffn(h, pick, weight, params, cfg)
    want = _plain(h, pick, weight, params, cfg)
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("name", SHARES)
def test_padding_is_keyed_as_on_no_held_expert(name):
    """Tokens that are not counted (a bucket's or last chunk's padding)
    leave the prefix: every token routed to held experts, a third of them
    counted, and the short buffer still holds the live rows."""
    n, cfg = SHARES[name]
    short, whole = ep._row_prefixes(n * cfg.top_k, cfg)
    h, params, cfg = _steered(name, n * cfg.top_k)
    counted = jnp.arange(n) < short // cfg.top_k
    y, counts = ep.dropless_moe(h, params, cfg, counted)
    assert int(counts["carried"]) == short
    everyone, all_counts = ep.dropless_moe(h, params, cfg)
    assert int(all_counts["carried"]) == whole
    live = np.asarray(counted)
    np.testing.assert_allclose(y[live], everyone[live], atol=1e-5)


@pytest.mark.parametrize("name", SHARES)
def test_a_decode_step_keeps_its_one_buffer(name):
    """At a step's assignments the rule is ``_row_buffer`` and no branch is
    built: the jaxpr has no ``cond`` and ``carried`` is a plain number."""
    _n, cfg = SHARES[name]
    rows = STEPS[name]
    assert ep._row_prefixes(rows, cfg) == (ep._row_buffer(rows),)
    assert ep._row_buffer(rows) % 256 == 128       # 128-row tiles
    params = ep.init_expert_share(jax.random.key(0), D, cfg)
    h = jnp.zeros((rows // cfg.top_k, D))
    text = str(jax.make_jaxpr(lambda h, p: ep.dropless_moe(h, p, cfg)[0])(
        h, params))
    assert "cond[" not in text
    _y, counts = ep.dropless_moe(h, params, cfg)
    assert counts["carried"] == ep._row_buffer(rows)
    assert isinstance(counts["carried"], int)


@pytest.mark.parametrize("name, tokens, want", [
    # ~285 rows an expert: 45 tiles of 256 (the share and an eighth of it)
    ("granite", 2048, (11520, 20608)),
    # its bucket of 1,024, ~142 an expert: 25 tiles of 256
    ("granite", 1024, (6400, 10368)),
    # ~66 and ~8 rows an expert: the share and 1,024 rows, tiles of 128
    ("glm", 2048, (2176, 16512)),
    ("longcat", 512, (1152, 6272)),
    ("longcat", 256, (1152, 3200)),
    # a bucket no longer than a decode step's lanes: the one buffer
    ("longcat", 128, (1664,)),
])
def test_the_chunk_shapes_get_the_lengths_the_chip_chose(name, tokens, want):
    _n, cfg = SHARES[name]
    got = ep._row_prefixes(tokens * cfg.top_k, cfg)
    assert got == want and got[-1] == ep._row_buffer(tokens * cfg.top_k)
    if len(got) == 2:
        # an odd multiple of its tile, so that the compiler takes that tile
        tile = 256 if name == "granite" else 128
        assert got[0] % (2 * tile) == tile


def test_a_share_that_holds_every_expert_builds_no_branch():
    _n, cfg = SHARES["granite"]
    cfg = dataclasses.replace(cfg, held_count=72)
    assert ep._row_prefixes(20480, cfg) == (ep._row_buffer(20480),)
