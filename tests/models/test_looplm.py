"""LoopLM (models/looplm.py) against its plain reference
(benchmark/reference/looplm.py) on seeded weights at a tiny size: loss,
per-pass cross-entropies, exit probabilities, every gradient leaf, three
AdamW steps; what the loop means for the gradient; what ``remat="layer"``
keeps of an application and what it runs again; the exit distribution;
the trainer through ``cli.train``."""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import train_lm as drv
from benchmark.reference import looplm as ref
from hyperspace_tpu.models import looplm as M
from hyperspace_tpu.telemetry import registry

MODEL_KEYS = ("hidden_size", "intermediate_size", "vocab_size",
              "num_hidden_layers", "num_attention_heads", "head_dim",
              "total_ut_steps", "rms_norm_eps", "rope_theta")


def _setup(seed=5, **cfg_kw):
    cfg = M.LoopLMConfig(**cfg_kw)
    model = {k: getattr(cfg, k) for k in MODEL_KEYS}
    recipe = {"lr": cfg.lr, "b1": cfg.adam_b1, "b2": cfg.adam_b2,
              "eps": cfg.adam_eps, "weight_decay": cfg.weight_decay,
              "clip_norm": cfg.clip_norm, "beta": cfg.entropy_beta}
    weights = ref.init_weights(seed, model)
    stream = jax.random.randint(jax.random.PRNGKey(1), (1000,), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    return cfg, model, recipe, weights, stream


def _program_steps(cfg, weights, stream, steps):
    """(losses, first step's stats, first gradient by the reference's
    names, final parameters by the reference's names)."""
    tree = drv.to_program_tree(weights, cfg.num_hidden_layers)
    opt, state = M.init_state(cfg, 0, params=tree)
    tokens = M.batch_at(stream, jnp.int32(0), cfg)
    grads = jax.grad(lambda p: M.loss_fn(cfg, p, tokens)[0])(state.params)
    losses, first = [], None
    for i in range(steps):
        state, loss = M.train_step(cfg, opt, state, stream)
        losses.append(float(loss))
        if i == 0:
            first = M.read_stats(cfg, state.stats)
    return (losses, first, drv.from_program_tree(grads),
            drv.from_program_tree(state.params))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# float32 lanes: both sides at float32 (on the CPU a float32 matmul is
# float32), so only the order of sums differs.  The bf16 lane rounds each
# matmul operand to 8 bits of mantissa: 2^-9 a value, shrunk by the sums
# over 64 or more products, grown by 8 layer applications in a row.
@pytest.mark.parametrize("precision,tol_loss,tol_grad,tol_step", [
    ("f32", 1e-6, 2e-5, 1e-5), ("bf16", 5e-4, 3e-2, 2e-2)])
def test_program_follows_the_reference(precision, tol_loss, tol_grad,
                                       tol_step):
    cfg, model, recipe, weights, stream = _setup(precision=precision)
    batches = [np.asarray(M.batch_at(stream, jnp.int32(i), cfg))
               for i in range(3)]
    want = ref.train_steps(weights, batches, model, recipe)
    losses, first, grads, params = _program_steps(cfg, weights, stream, 3)

    for a, b in zip(losses, want["losses"]):
        assert a == pytest.approx(b, rel=tol_loss)
    assert first["loss"] == pytest.approx(want["losses"][0], rel=tol_loss)
    assert first["ce"] == pytest.approx(want["ce"], rel=tol_loss)
    assert first["exit_prob"] == pytest.approx(want["exit_prob"],
                                               rel=10 * tol_loss)
    assert first["grad_norm"] == pytest.approx(want["grad_norm"],
                                               rel=tol_grad)
    assert sorted(grads) == sorted(want["grads"])
    for name in sorted(grads):  # every leaf
        assert _rel(grads[name], want["grads"][name]) < tol_grad, name
    # three optimizer steps: each leaf's change against the reference's
    start = {k: np.asarray(v) for k, v in weights.items()}
    for name in sorted(params):
        moved = float(np.linalg.norm(params[name] - start[name]))
        assert moved == pytest.approx(want["change_norms"][name],
                                      rel=tol_step, abs=1e-9), name


def test_shared_weight_gradient_is_the_sum_over_its_four_uses():
    """With each pass given its own copy of the layers' weights (the
    reference's parts, unshared by hand), the gradients of the T copies
    add up to the program's gradient of the one shared stack."""
    cfg, model, recipe, weights, stream = _setup()
    tokens = np.asarray(M.batch_at(stream, jnp.int32(0), cfg))[0]
    passes, n = cfg.total_ut_steps, cfg.num_hidden_layers
    kw = dict(heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
              eps=cfg.rms_norm_eps, theta=cfg.rope_theta)

    def unshared_loss(copies, shared):
        h = shared["embed"][tokens[:-1]]
        ces, lams = [], []
        for t in range(passes):
            for i in range(n):
                h = ref.layer(h, copies[t][i], **kw)
            h = ref.rms_norm(h, shared["final_norm"], cfg.rms_norm_eps)
            ces.append(ref.token_ce(h, shared["head"], tokens[1:]))
            lams.append(jax.nn.sigmoid(h @ shared["gate_w"]
                                       + shared["gate_b"]))
        p = ref.exit_distribution(jnp.stack(lams))
        return ref.loss_terms(jnp.stack(ces), p, cfg.entropy_beta)[0]

    copies = [[ref.layer_of(weights, i) for i in range(n)]
              for _ in range(passes)]
    with jax.default_matmul_precision("highest"):
        per_use = jax.grad(unshared_loss)(copies, weights)
    _, _, grads, _ = _program_steps(cfg, weights, stream, 1)
    for i in range(n):
        for leaf in ref.LAYER_MATS + ref.LAYER_GAINS:
            uses = [np.asarray(per_use[t][i][leaf]) for t in range(passes)]
            assert _rel(grads[f"l{i}.{leaf}"], sum(uses)) < 2e-5, (i, leaf)
            # and no single use is the whole of it
            assert _rel(grads[f"l{i}.{leaf}"], uses[-1]) > 1e-2, (i, leaf)


def test_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 50)) * 3,
                         jnp.float32)
    p = np.exp(np.asarray(M.exit_log_probs(logits), np.float64))
    lam = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-5)
    np.testing.assert_allclose(p[3], 1.0 - p[:3].sum(axis=0), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(
        p, np.asarray(ref.exit_distribution(jnp.asarray(lam, jnp.float32))),
        rtol=1e-5, atol=1e-8)
    # extreme gates stay finite in log space
    far = M.exit_log_probs(jnp.asarray([[80.0], [-80.0], [0.0]], jnp.float32))
    assert np.isfinite(np.asarray(far)).all()


def test_one_pass_is_a_plain_transformer():
    """T = 1: p = 1, no entropy, the loss is the mean cross-entropy of a
    causal transformer's one forward pass."""
    cfg, model, recipe, weights, stream = _setup(total_ut_steps=1)
    tokens = M.batch_at(stream, jnp.int32(0), cfg)
    tree = drv.to_program_tree(weights, cfg.num_hidden_layers)
    loss, (ce, p) = M.loss_fn(cfg, tree, tokens)
    assert np.asarray(p) == pytest.approx([1.0])
    assert float(loss) == pytest.approx(float(ce[0]), rel=1e-6)
    with jax.default_matmul_precision("highest"):
        want_ce, want_p = ref.forward(weights, np.asarray(tokens)[0], model)
    assert float(loss) == pytest.approx(float(jnp.mean(want_ce)), rel=1e-6)
    assert np.asarray(want_p) == pytest.approx(1.0)


def test_recomputation_changes_no_gradient():
    cfg, _, _, weights, stream = _setup(precision="bf16")
    off = M.LoopLMConfig(precision="bf16", remat="none")
    tokens = M.batch_at(stream, jnp.int32(0), cfg)
    tree = drv.to_program_tree(weights, cfg.num_hidden_layers)
    g_on = jax.grad(lambda p: M.loss_fn(cfg, p, tokens)[0])(tree)
    g_off = jax.grad(lambda p: M.loss_fn(off, p, tokens)[0])(tree)
    for a, b in zip(jax.tree_util.tree_leaves(g_on),
                    jax.tree_util.tree_leaves(g_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- what remat="layer" keeps: the kernel in interpret mode ------------------

KEPT_GAUGE = "looplm/remat_kept_bytes"
LANES = {"bf16": np.dtype(jnp.bfloat16), "f32": np.dtype("float32")}


@pytest.fixture
def interp(monkeypatch):
    monkeypatch.setenv("HYPERSPACE_KERNELS", "interpret")


def _grad_jaxpr(precision, **cfg_kw):
    cfg, _, _, weights, stream = _setup(precision=precision, **cfg_kw)
    tokens = M.batch_at(stream, jnp.int32(0), cfg)
    tree = drv.to_program_tree(weights, cfg.num_hidden_layers)
    return cfg, jax.make_jaxpr(jax.grad(
        lambda p: M.loss_fn(cfg, p, tokens)[0]))(tree).jaxpr


def _walk(jaxpr, times=1):
    """(equation, how often it runs a step) under ``jaxpr``: a scan's
    body counts ``length`` times."""
    for eqn in jaxpr.eqns:
        yield eqn, times
        inside = times * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else times
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner, inside)


def _forward_stacks(jaxpr):
    """(length, what the forward loop stacks for the backward, one slice
    an application): the first scan's outputs beyond its carry."""
    loop = next(e for e, _ in _walk(jaxpr) if e.primitive.name == "scan")
    return loop.params["length"], loop.outvars[loop.params["num_carry"]:]


def _kernel_calls(jaxpr):
    calls = {}
    for eqn, times in _walk(jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            calls[name] = calls.get(name, 0) + times
    return calls


# (lane, S, operation by operation?, the other form, each leaf within).
# bf16 lane: the loops' bodies compiled, as the program runs them.  float32
# lane against the parent's form: operation by operation
# (``jax.disable_jit``) at a shorter sequence, because XLA's CPU compiler
# fuses the interpreter's kernel into the loop's body, and rounds it,
# differently in a body that holds it once and one that holds it twice
# (1e-7 of the largest value); a Mosaic kernel on the chip is one program
# wherever it is called.  float32 lane against no checkpoint at all:
# autodiff sums a value's cotangents in another order there, with or
# without this policy, kernel or twin, so that pair is held to rounding
SAME_ARITHMETIC = {
    "bf16, remat=none": ("bf16", 64, False, "none", 0.0),
    "bf16, the parent's checkpoint": ("bf16", 64, False, "parent", 0.0),
    "f32, the parent's checkpoint": ("f32", 16, True, "parent", 0.0),
    "f32, remat=none: sums in another order": ("f32", 64, False, "none",
                                               1e-5),
}


@pytest.mark.parametrize("precision,seq,whole_ops,other,within",
                         SAME_ARITHMETIC.values(), ids=SAME_ARITHMETIC.keys())
def test_kept_flash_results_change_no_bit(interp, monkeypatch, precision,
                                          seq, whole_ops, other, within):
    """Keeping the flash call's output and row statistics is the same
    arithmetic: loss and every gradient leaf equal, bit for bit, those of
    no recomputation at all and of a checkpoint that keeps the input
    alone (the parent's form: no policy, the kernel run twice)."""
    cfg, _, _, weights, stream = _setup(precision=precision,
                                        sequence_length=seq)
    tokens = M.batch_at(stream, jnp.int32(0), cfg)
    tree = drv.to_program_tree(weights, cfg.num_hidden_layers)

    def loss_and_grads(cfg):
        with jax.disable_jit(whole_ops):
            return jax.value_and_grad(
                lambda p: M.loss_fn(cfg, p, tokens)[0])(tree)

    loss, grads = loss_and_grads(cfg)
    if other == "none":
        cfg = dataclasses.replace(cfg, remat="none")
    else:
        monkeypatch.setattr(M, "_keep_flash_results", lambda: None)
    want_loss, want = loss_and_grads(cfg)
    assert np.isfinite(float(loss))
    assert float(loss) == pytest.approx(float(want_loss), rel=within, abs=0)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(a)).max() > 0, jax.tree_util.keystr(path)
        if within:
            assert _rel(a, b) < within, jax.tree_util.keystr(path)
        else:
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("precision", ["bf16", "f32"])
def test_backward_never_runs_the_flash_forward_call_again(
        interp, monkeypatch, precision):
    """A step holds T·L forward flash calls, T·L ``dq`` and T·L ``dkv``;
    the checkpoint that keeps the input alone holds 2·T·L forward calls."""
    cfg, jaxpr = _grad_jaxpr(precision)
    each = cfg.total_ut_steps * cfg.num_hidden_layers
    assert _kernel_calls(jaxpr) == {
        "flash_dot_fwd": each, "flash_dot_dq": each, "flash_dot_dkv": each}
    monkeypatch.setattr(M, "_keep_flash_results", lambda: None)
    assert _kernel_calls(_grad_jaxpr(precision)[1]) == {
        "flash_dot_fwd": 2 * each, "flash_dot_dq": each,
        "flash_dot_dkv": each}


@pytest.mark.parametrize("precision", ["bf16", "f32"])
def test_an_application_keeps_its_input_and_the_flash_results(interp,
                                                              precision):
    """What the forward loop stacks for the backward, one slice an
    application: the input ``[S, d]``, the flash output ``[H, S, D]`` on
    the compute lane, the rows' log-sum-exp ``f32[H, S]`` and the
    application's index; never the kernel's ``[H, S, 128]`` statistics
    tile, q, k, v or anything of the feed-forward.  The gauge reads the
    two kept arrays' bytes."""
    cfg, jaxpr = _grad_jaxpr(precision)
    each = cfg.total_ut_steps * cfg.num_hidden_layers
    heads, seq, dh = (cfg.num_attention_heads, cfg.sequence_length,
                      cfg.head_dim)
    length, stacked = _forward_stacks(jaxpr)
    assert length == each
    index_dtype = jnp.arange(1).dtype   # int64 under the tests' x64
    found = sorted((v.aval.shape[1:], v.aval.dtype) for v in stacked)
    assert found == sorted([
        ((), index_dtype), ((seq, cfg.hidden_size), np.dtype("float32")),
        ((heads, seq, dh), LANES[precision]),
        ((heads, seq), np.dtype("float32"))])
    want = heads * seq * (dh * LANES[precision].itemsize + 4)
    assert registry.default_registry().snapshot()[KEPT_GAUGE] == want


def test_the_xla_twin_names_nothing_and_keeps_the_input_alone():
    """No kernel (the CPU's dense twin): the gauge reads 0 and the loop
    stacks the input and the index only."""
    cfg, jaxpr = _grad_jaxpr("bf16")
    _, stacked = _forward_stacks(jaxpr)
    assert sorted(v.aval.ndim for v in stacked) == [1, 3]
    assert _kernel_calls(jaxpr) == {}
    assert registry.default_registry().snapshot()[KEPT_GAUGE] == 0


@pytest.mark.parametrize("seq,heads,dh,precision,want", [
    (64, 4, 16, "bf16", 9216), (64, 4, 16, "f32", 17408),
    (40, 2, 32, "bf16", 5440)])
def test_kept_bytes_gauge(interp, seq, heads, dh, precision, want):
    """The gauge is what jax's own split of an application granted the
    policy (at Ouro's widths 16 × 4096 × (128 × 2 + 4) = 17,039,360); a
    configuration that recomputes nothing leaves it where it was."""
    registry.set_gauge(KEPT_GAUGE, -1)
    _grad_jaxpr(precision, sequence_length=seq, num_attention_heads=heads,
                num_key_value_heads=heads, head_dim=dh)
    assert registry.default_registry().snapshot()[KEPT_GAUGE] == want
    registry.set_gauge(KEPT_GAUGE, -1)
    _grad_jaxpr(precision, remat="none")
    assert registry.default_registry().snapshot()[KEPT_GAUGE] == -1


# the head runs once over the T·S rows of all four passes, ``block`` rows
# at a time; (S, block): what the rows do to the blocks
HEAD_CASES = {
    "S a multiple of the block": (64, 16),
    "S not, T*S is: a block straddles two passes": (40, 16),
    "neither: the last block is padded": (25, 16),
    "S under one block, T*S two blocks": (8, 16),
    "all T*S rows under one block": (3, 16),
}


@pytest.mark.parametrize("seq,block", HEAD_CASES.values(),
                         ids=HEAD_CASES.keys())
def test_long_head_is_computed_in_row_blocks(monkeypatch, seq, block):
    """The head's cross-entropy runs over all passes' rows a block at a
    time; each pass's per-token cross-entropy, the exit probabilities and
    every leaf's gradient are the plain reference's at float32."""
    cfg, model, recipe, weights, stream = _setup(sequence_length=seq)
    monkeypatch.setattr(M, "HEAD_BLOCK_ROWS", block)
    tokens = M.batch_at(stream, jnp.int32(0), cfg)
    tree = drv.to_program_tree(weights, cfg.num_hidden_layers)
    ce, log_p = M.forward(cfg, tree, tokens[0])
    assert ce.shape == log_p.shape == (cfg.total_ut_steps, seq)
    host = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):   # jitted: one compile
        want_ce, want_p = jax.jit(
            lambda w: ref.forward(w, host[0], model))(weights)
    np.testing.assert_allclose(ce, want_ce, rtol=1e-5)
    np.testing.assert_allclose(np.exp(log_p), want_p, rtol=1e-5, atol=1e-8)
    loss, grads = jax.value_and_grad(
        lambda p: M.loss_fn(cfg, p, tokens)[0])(tree)
    want_loss, _, _, want = jax.jit(lambda w: ref.loss_and_grads(
        w, host, model, recipe["beta"]))(weights)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    grads = drv.from_program_tree(grads)
    assert sorted(grads) == sorted(want)
    for name in sorted(grads):  # every leaf
        assert _rel(grads[name], want[name]) < 2e-5, name


def _computations(hlo_text):
    """{computation: [instruction lines]} of an HLO module's text."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            cur = comps.setdefault(
                re.search(r"%?([\w.\-]+) \(", line).group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _under(comps, name, seen):
    """``name`` and every computation that an instruction under it names
    (``body=``, ``calls=``, ``branch_computations={…}`` and the like)."""
    if name not in seen:
        seen.add(name)
        for line in comps[name]:
            for called in set(re.findall(r"[\w.\-]+", line)) & comps.keys():
                _under(comps, called, seen)
    return seen


def test_layer_loop_holds_no_array_of_the_heads_shape(monkeypatch):
    """Compiled, the loop over the layer applications (forward and
    backward ``while``) has no ``[d, V]`` result anywhere in its body:
    what a ``lax.cond`` inside the loop closes over is added whole to a
    carry at every application, so the head stays out of the loop."""
    # sizes at which [d, V] is the head's shape alone: a block of logits
    # is [16, 320], the streams [S, d] = [48, 64]
    cfg, _, _, weights, stream = _setup(vocab_size=320, sequence_length=48)
    monkeypatch.setattr(M, "HEAD_BLOCK_ROWS", 16)
    head_shape = f"f32[{cfg.hidden_size},{cfg.vocab_size}]"
    tree = drv.to_program_tree(weights, cfg.num_hidden_layers)
    opt, state = M.init_state(cfg, 0, params=tree)
    text = M.train_step.lower(cfg, opt, state, stream).compile().as_text()
    assert head_shape in text   # the head's gradient, somewhere
    comps = _computations(text)
    loops = [line for lines in comps.values() for line in lines
             if re.search(r" while\(", line)
             and re.search(r'op_name="[^"]*ut_step\)*/while"', line)]
    assert len(loops) == 2, [ln[:200] for ln in loops]   # forward, backward
    for loop in loops:
        inside = _under(comps, re.search(r"body=%?([\w.\-]+)",
                                         loop).group(1), set())
        found = [line.strip()[:160] for name in inside
                 for line in comps[name]
                 if re.match(rf"\s*(?:ROOT )?%?[\w.\-]+ = "
                             rf"{re.escape(head_shape)}", line)]
        assert len(inside) > 3, inside   # the walk reached the nested calls
        assert not found, found


def test_batches_follow_the_stream_in_order_and_wrap():
    cfg = M.LoopLMConfig(sequence_length=64, sequences_per_step=2)
    stream = jnp.arange(1000, dtype=jnp.int32)
    host = drv.host_batches(np.arange(1000), 9, 64, 2)
    for i in (0, 1, 7, 8):   # step 7 wraps past the stream's end
        got = np.asarray(M.batch_at(stream, jnp.int32(i), cfg))
        np.testing.assert_array_equal(got, host[i])
        assert got[0, 0] == (i * 2 * 64) % 1000 and got.shape == (2, 65)
    assert np.asarray(M.batch_at(stream, jnp.int32(7), cfg)).min() == 0


def test_token_stream_is_a_packed_zipf_stream(tmp_path):
    from hyperspace_tpu.data import text

    kw = dict(num_tokens=1 << 16, vocab_size=512, doc_len_median=24.0,
              doc_len_min=4, doc_len_max=64)
    a = text.synthetic_token_stream(seed=3, **kw)
    assert a.dtype == np.int32 and a.shape == (1 << 16,)
    assert a.min() == text.EOD_ID == 0 and a.max() <= 511
    np.testing.assert_array_equal(a, text.synthetic_token_stream(seed=3, **kw))
    assert (a != text.synthetic_token_stream(seed=4, **kw)).any()
    counts = np.bincount(a, minlength=512)
    # a long-tailed unigram law: rank 1 about twice rank 2, ten times rank 10
    assert 1.6 < counts[1] / counts[2] < 2.4
    assert 7 < counts[1] / counts[10] < 13
    gaps = np.diff(np.flatnonzero(a == 0)) - 1   # document lengths
    assert gaps.min() >= 4 and gaps.max() <= 64
    assert 18 < np.median(gaps) < 30
    # written once, read back, and found by the CLI's loader
    path = text.ensure_token_stream(str(tmp_path / "s"), seed=3, **kw)
    stamp = (tmp_path / "s" / "tokens.npy").stat().st_mtime_ns
    assert text.ensure_token_stream(str(tmp_path / "s"), seed=3,
                                    **kw) == path
    assert (tmp_path / "s" / "tokens.npy").stat().st_mtime_ns == stamp
    got, source = text.load_token_stream(str(tmp_path / "s"))
    assert source == "disk"
    np.testing.assert_array_equal(got, a)


def test_lane_matmul_keeps_the_weights_gradient_in_float32():
    from hyperspace_tpu import precision

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 24, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)
    y = precision.BF16.matmul(x, w)
    assert y.dtype == jnp.float32 and y.shape == (3, 24, 16)
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_allclose(y, low(x) @ low(w), rtol=1e-5, atol=1e-5)
    dx, dw = jax.grad(lambda x, w: jnp.sum(
        precision.BF16.matmul(x, w) ** 2), argnums=(0, 1))(x, w)
    assert dx.dtype == jnp.float32 and dw.dtype == jnp.float32
    g = low(2 * y)
    np.testing.assert_allclose(dx, g @ low(w).T, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        dw, low(x).reshape(-1, 32).T @ g.reshape(-1, 16), rtol=1e-5,
        atol=1e-3)
    # the f32 preset is the plain product
    np.testing.assert_array_equal(precision.F32.matmul(x, w), x @ w)


# --- through cli.train -------------------------------------------------------

TINY = ["hidden_size=32", "intermediate_size=48", "num_attention_heads=2",
        "num_key_value_heads=2", "head_dim=16", "vocab_size=128",
        "num_hidden_layers=2", "sequence_length=32", "stream_tokens=4096"]


def test_cli_trains_from_the_published_yaml(capsys, tmp_path):
    """The repo's yaml is the published config.json whole; cut to a test
    size by overrides, it trains through run_loop, logs, and sets the
    looplm/* gauges at the log boundary."""
    import os

    from hyperspace_tpu.cli import train as T
    from hyperspace_tpu.telemetry import registry

    yaml_path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "configs", "looplm_ouro_2p6b.yaml")
    log = tmp_path / "run.jsonl"
    assert T.main(["looplm", "--yaml", yaml_path, *TINY, "steps=6",
                   "eval_every=3", f"log={log}", "precision=f32"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["workload"] == "looplm" and out["steps"] == 6
    assert out["source"] == "synthetic" and out["tokens_per_step"] == 32
    assert len(out["ce"]) == 4 and sum(out["exit_prob"]) == pytest.approx(1.0)
    assert np.isfinite(out["loss"])
    records = [json.loads(ln) for ln in log.read_text().splitlines()]
    assert [r["step"] for r in records if "loss" in r] == [3, 6]
    snap = registry.default_registry().snapshot()
    assert snap["looplm/ut_steps"] == 4
    assert snap["looplm/tokens_per_step"] == 32
    assert [snap[f"looplm/exit_prob_t{t}"] for t in (1, 2, 3, 4)] == \
        pytest.approx(out["exit_prob"])
    assert [snap[f"looplm/ce_t{t}"] for t in (1, 2, 3, 4)] == \
        pytest.approx(out["ce"])
    assert 1.0 <= snap["looplm/expected_exit_step"] <= 4.0


@pytest.mark.parametrize("override,says", [
    ("model_type=llama", "model_type"),
    ("use_sliding_window=True", "use_sliding_window"),
    ('layer_types=["full_attention", "sliding_attention"]', "layer_types"),
    ("num_key_value_heads=1", "multi-head"),
    ("max_position_embeddings=16", "max_position_embeddings"),
    ("tie_word_embeddings=true", "untied"),
    ("no_such_key=1", "unknown option"),
])
def test_cli_refuses_what_the_trainer_does_not_run(override, says):
    from hyperspace_tpu.cli import train as T

    with pytest.raises(SystemExit) as e:
        T.main(["looplm", *TINY, override, "steps=1"])
    assert says in str(e.value)
