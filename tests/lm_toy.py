"""What the language-model test files share.  A toy model's arithmetic
is nothing; a test pays for dispatch and compilation.  So what a test
walks position by position it calls through ``jitted`` with ids,
positions, lengths and tables as arrays of ONE shape, a reference it asks
again and again it asks through ``ref_logits`` at ONE padded length, and
a reference's weights are drawn once.  Each file keeps its model's
dictionary ``M`` and its own cases.
"""

import collections
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.telemetry import MetricsRegistry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PS = 4      # the page size of every toy cache but the block model's


@functools.cache
def load_reference(name):
    """``benchmarks/references/<name>.py`` as a module."""
    spec = importlib.util.spec_from_file_location(
        name + "_reference",
        os.path.join(REPO, "benchmarks", "references", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_DRAWN = {}


def draw(ref, M, seed):
    """``ref.init_weights(M, seed, float32)``, once a (reference, ``M``,
    seed): the reference compiles its draw anew at every call (seconds,
    whatever the size).  The tree is shared: read it, do not write it."""
    key = (ref.__name__, json.dumps(M, sort_keys=True), seed)
    if key not in _DRAWN:
        _DRAWN[key] = ref.init_weights(M, seed, jnp.float32)
    return _DRAWN[key]


def small_cfg(**kw):
    """The GPT-2 block at the size the serving files build engines of."""
    return T.TransformerConfig(**{**dict(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=32, mlp_dim=64,
        max_seq_len=64, remat=False), **kw})


def fixtures(name, M, seed, seq_len=None, pad=None):
    """The module-scoped fixtures an LM file starts from, to be bound to
    THESE names: ``ref`` (the reference), ``weights`` (its draw),
    ``params`` (the program's tree) and, with ``seq_len``, ``seq`` (seeded
    ids) and ``ref_logits`` (the reference's logits over them)."""
    fixture = pytest.fixture(scope="module")

    def ref():
        return load_reference(name)

    def weights(ref):
        return draw(ref, M, seed)

    def params(ref, weights):
        return ref.program_tree(weights)

    def seq():
        return np.random.default_rng(5).integers(
            0, M["vocab_size"], seq_len).astype(np.int32)

    def seq_logits(ref, weights, seq):
        return ref_logits(ref, weights, M, seq, pad)

    made = (ref, weights, params) + ((seq, seq_logits) if seq_len else ())
    return tuple(fixture(f) for f in made)


def pools(cfg, pages, slots=2, page_size=PS):
    """(K pool, V pool, {part: state pool ``[layers, slots, ...]``}) of
    ``cfg``, as the serving cache lays them out."""
    kc, vc = PA.init_kv_pages(cfg.cache_layers, cfg.kv_heads, pages,
                              page_size, cfg.head_dim)
    state = {part: jnp.zeros((layers, slots, *shape), jnp.float32)
             for part, (layers, shape) in cfg.state_parts.items()}
    return kc, vc, state


def engine(cfg, params, registry=None, **serving):
    return ServingEngine(cfg, params, ServingConfig(**serving),
                         registry=registry or MetricsRegistry("toy"))


def serve_on_route(monkeypatch, route, cfg, params, prompts, news, **serving):
    """``prompts`` through a fresh engine, the first two steps ahead of
    the rest, whose decode step takes the Mamba-2 recurrence by ``route``
    ("kernel": interpreted here; "reference": ``ssd_step``).  Returns
    (each request's tokens, the routing census of what the engine
    traced).  The route is traced INTO the programs, so this engine
    shares none with another."""
    from paddle_tpu.ops import mamba2, pallas
    from paddle_tpu.serving import engine as E

    routed = mamba2.ssd_pool_step
    monkeypatch.setattr(E, "_FN_MEMO", {})
    monkeypatch.setattr(
        mamba2, "ssd_pool_step",
        lambda *a, impl=None, **kw: routed(*a, impl=route, **kw))
    with pallas.capture_routes() as routes:
        eng = engine(cfg, params, **serving)
        ids = [eng.submit(prompts[0], news[0])]
        eng.step()
        eng.step()
        ids += [eng.submit(p, n) for p, n in zip(prompts[1:], news[1:])]
        eng.run_until_idle()
    got = {r.id: r.tokens for r in eng.results()}
    return [got[i] for i in ids], routes


_PADDED = {}


def ref_logits(ref, weights, M, ids, pad):
    """The reference's logits over ``ids``, from one forward compiled at
    the padded length ``pad`` a weights object (the models are causal:
    what lies right of a position does not reach it)."""
    held = _PADDED.get(id(weights))
    if held is None:    # the weights ride along: their id stays theirs
        held = _PADDED[id(weights)] = (weights, jax.jit(
            lambda ids: ref.logits_fn(weights, ids, M)))
    buf = np.zeros((pad,), np.int32)
    buf[:len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        return np.asarray(held[1](jnp.asarray(buf)))[:len(ids)]


def greedy(ref, weights, M, prompt, n, pad):
    """The reference's greedy continuation of ``prompt``, ``n`` tokens."""
    out = list(prompt)
    for _ in range(n):
        out.append(int(np.argmax(ref_logits(ref, weights, M, out, pad)[-1])))
    return out[len(prompt):]


_JITTED = {}


def jitted(fn, cfg, **static):
    """``jax.jit`` of a function of ``models/transformer.py`` (``forward*``,
    ``init_params``) with ``cfg`` and ``static`` (``attn_impl``, ``mesh``)
    closed over, one a key: tests of one model share its executables."""
    key = (fn, cfg, tuple(sorted(static.items())))
    if key not in _JITTED:
        _JITTED[key] = jax.jit(functools.partial(fn, cfg, **static))
    return _JITTED[key]


def forward_argmax(cfg, params, prompt, tokens, pad):
    """The argmax of ONE ``forward`` over prompt + tokens at each served
    position, at the padded length ``pad`` (causal).  Greedy tokens equal
    it where they equal a forward re-run a step: the first mismatch shows."""
    full = list(prompt) + list(tokens)
    logits = jitted(T.forward, cfg)(
        params, jnp.asarray([full + [0] * (pad - len(full))]))
    return [int(t) for t in jnp.argmax(
        logits[0, len(prompt) - 1:len(full) - 1], axis=-1)]


def walk_positions(cfg, params, seq, want, p_len, padded, attn_impl, tol):
    """Prefill ``p_len`` tokens of ``seq`` as row 1 of a batch padded to
    ``padded`` (row 0: another prompt of five), K/V into pages and state
    into slot rows, then decode the rest through ONE compiled step: every
    position's logits are ``want``'s to ``tol``, and row 0, idle, keeps its
    state bit for bit.  Returns (the prefill's K stack, the state parts it
    handed back, the state after the last step)."""
    per_row = -(-len(seq) // PS) + 1
    kc, vc, state = pools(cfg, pages=2 * per_row + 1)
    ids = np.zeros((2, padded), np.int32)
    ids[0, :5] = seq[10:15]
    ids[1, :p_len] = seq[:p_len]
    lens = jnp.asarray([5, p_len])
    logits, ks, vs, extras = jitted(T.forward_prefill, cfg)(
        params, jnp.asarray(ids), lens)
    np.testing.assert_allclose(np.asarray(logits[1]), want[p_len - 1],
                               atol=tol, rtol=tol)
    table = jnp.arange(1, 2 * per_row + 1, dtype=jnp.int32).reshape(2, -1)
    kc, vc = PA.write_prefill_kv(kc, vc, ks, vs, table, lens)
    handed = set(extras["state"])
    state = {n: extras["state"][n] for n in state}   # slot = row
    idle = {n: np.asarray(v[:, 0]) for n, v in state.items()}
    decode = jitted(T.forward_decode, cfg, attn_impl=attn_impl)
    for pos in range(p_len, len(seq)):
        logits, kc, vc, extras = decode(
            params, jnp.asarray([0, seq[pos]]), jnp.asarray([0, pos]),
            jnp.asarray([0, pos + 1]), table.at[0].set(0), kc, vc,
            state=state)
        state = extras["state"]
        np.testing.assert_allclose(np.asarray(logits[1]), want[pos],
                                   atol=tol, rtol=tol)
    for n, v in state.items():
        np.testing.assert_array_equal(np.asarray(v[:, 0]), idle[n])
    return ks, handed, state


def traced(serve):
    """``serve()`` under span tracing: (what it returned, its spans by
    name); the tracer is off and empty again afterwards."""
    tracer = tracing.configure_tracing(enabled=True)
    tracer.clear()
    try:
        out, spans = serve(), collections.defaultdict(list)
        for s in tracer.spans:
            spans[s.name].append(s)
    finally:
        tracing.configure_tracing(enabled=False)
        tracer.drain()
    return out, spans


def cli_serves_the_forward(monkeypatch, capsys, vocab, layers, parts):
    """``python -m paddle_tpu.serving --random --model_json <parts>`` in
    process (it leaves no flag or tracer behind) serves the greedy tokens
    of the same seeded weights' forward.  Returns the config."""
    import io

    from paddle_tpu.serving.__main__ import main

    monkeypatch.setattr("sys.stdin", io.StringIO("5 17 3\n"))
    assert main(["--random", "--vocab", str(vocab), "--embed", "32",
                 "--layers", str(layers), "--heads", "4", "--max_new_tokens",
                 "4", "--seed", "7", "--model_json", json.dumps(parts)]) == 0
    served = [int(t) for t in
              capsys.readouterr().out.strip().split(":")[1].split()]
    cfg = T.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=4, embed_dim=32,
        mlp_dim=128, max_seq_len=256, remat=False, **parts)
    weights = T.init_params(cfg, jax.random.key(7))
    assert served == forward_argmax(cfg, weights, [5, 17, 3], served, 8)
    return cfg
