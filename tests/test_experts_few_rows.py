"""`RoutedExperts._few_rows`, the path a decode step takes (no more rows
than experts, at least as many token-expert pairs as experts): every row
meets every expert in one batched product a projection and the top-k's
weights, zero for an expert not chosen, fold into the down projection's
left operand.

Tiny widths on the CPU: 8 experts, 3 active. Rows 3, 6 and 8 take the
few-rows path by their shape, rows 2 and 48 the sorted `ragged_dot` one;
each path's result on the OTHER path's inputs comes from calling
`_few_rows` directly, or from tiling the rows to 48 (more rows than
experts) and keeping the first copy. The plain reference is
`sparse_decoder_reference.expert_mix`.

Tolerances. float32 weights at the suite's "highest" matmul precision
differ by summation order alone: 1e-5 of the result's scale. bfloat16
weights: gate, up and the down projection's left operand are each
rounded to bfloat16 (2^-9 relative), so program and float32 reference
differ by some 1e-2 of the scale; the two PATHS round at the same
points but for the top-k weight (before the rounding here, after the
float32 product there), so they differ by as much.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import sparse_decoder_reference as ref
from bigdl_tpu.nn.experts import RoutedExperts, route

E, K = 8, 3
FEW, SORTED = (3, 6, 8), (2, 48)
CFG = {"experts": E, "top_k": K}
TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def layer(d=64, h=32):
    return RoutedExperts(d, h, E, K)


def draw(seed, rows, router, dtype, d=64, h=32):
    """(params in `dtype`, x [rows, d] float32, router logits [rows, E]
    float32). "skewed": every row prefers experts 0 and 1 by a wide
    margin and never looks at 6 and 7."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    params = {"wg": 0.2 * jax.random.normal(k[0], (E, d, h)),
              "wu": 0.2 * jax.random.normal(k[1], (E, d, h)),
              "wd": 0.2 * jax.random.normal(k[2], (E, h, d))}
    params = {n: w.astype(dtype) for n, w in params.items()}
    x = jax.random.normal(k[3], (rows, d))
    logits = jax.random.normal(k[4], (rows, E))
    if router == "skewed":
        logits = logits + jnp.asarray([6., 5., 0, 0, 0, 0, -9., -9.])
    return params, x, logits


def widened(params):
    return {n: w.astype(jnp.float32) for n, w in params.items()}


def few_rows(mod, params, x, logits):
    experts, weights = route(logits, K)
    return mod._few_rows(params, x.astype(params["wg"].dtype), experts,
                         weights)


def sorted_rows(mod, params, x, logits):
    """The sorted path's result for these rows, whatever their count:
    tiled to 48 rows, which is more rows than experts."""
    n = x.shape[0]
    reps = -(-48 // n)
    x, logits = jnp.tile(x, (reps, 1)), jnp.tile(logits, (reps, 1))
    assert uses_ragged_dot(mod, params, x, logits)
    y, _ = mod.apply_routed(params, x, logits)
    return y[:n]


def uses_ragged_dot(mod, params, x, logits):
    return "ragged_dot" in str(jax.make_jaxpr(mod.apply_routed)(
        params, x, logits))


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * np.abs(want).max())


# ------------------------------------------------ (a) against the reference
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("router", ["uniform", "skewed"])
@pytest.mark.parametrize("rows", FEW)
def test_few_rows_matches_reference_and_sorted_path(rows, router, dtype):
    mod = layer()
    params, x, logits = draw(rows, rows, router, dtype)
    assert not uses_ragged_dot(mod, params, x, logits)
    got, chosen = mod.apply_routed(params, x, logits)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    np.testing.assert_array_equal(np.asarray(chosen),
                                  np.asarray(route(logits, K)[0]))
    x_seen = x.astype(dtype).astype(jnp.float32)
    close(got, ref.expert_mix(CFG, widened(params), x_seen, logits),
          TOL[dtype])
    close(got, sorted_rows(mod, params, x, logits), TOL[dtype])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("router", ["uniform", "skewed"])
@pytest.mark.parametrize("rows", SORTED)
def test_sorted_path_matches_reference_and_few_rows_form(rows, router,
                                                         dtype):
    """The sorted path gives what it gave: the reference's numbers, and
    the few-rows form's on the same rows."""
    mod = layer()
    params, x, logits = draw(rows, rows, router, dtype)
    assert uses_ragged_dot(mod, params, x, logits)
    got, _ = mod.apply_routed(params, x, logits)
    assert got.dtype == jnp.float32
    x_seen = x.astype(dtype).astype(jnp.float32)
    close(got, ref.expert_mix(CFG, widened(params), x_seen, logits),
          TOL[dtype])
    close(got, few_rows(mod, params, x, logits), TOL[dtype])


# ------------------------------------- (b) an unchosen expert adds nothing
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", FEW)
def test_unchosen_experts_add_nothing_bit_for_bit(rows, dtype):
    mod = layer()
    params, x, logits = draw(20 + rows, rows, "skewed", dtype)
    chosen = set(np.asarray(route(logits, K)[0]).reshape(-1).tolist())
    idle = [e for e in range(E) if e not in chosen]
    assert idle, "the skewed router leaves experts 6 and 7 to no row"
    scale = jnp.ones((E, 1, 1)).at[jnp.asarray(idle)].set(1e3)
    loud = {n: (w.astype(jnp.float32) * scale).astype(w.dtype)
            for n, w in params.items()}
    assert not uses_ragged_dot(mod, params, x, logits)
    want, _ = mod.apply_routed(params, x, logits)
    got, _ = mod.apply_routed(loud, x, logits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------- (c) a float32 sum over all E x h
@pytest.mark.parametrize("router", ["uniform", "skewed"])
@pytest.mark.parametrize("rows", FEW)
def test_bf16_weights_accumulate_in_float32(rows, router):
    """With bfloat16 weights the result is the float32 product of the
    same bfloat16-rounded operands to 1e-5: a bfloat16 accumulator over
    E x h = 1024 terms would miss by 1e-2."""
    mod = layer(d=64, h=128)
    params, x, logits = draw(40 + rows, rows, router, "bfloat16", h=128)
    got = few_rows(mod, params, x, logits)
    assert got.dtype == jnp.float32
    experts, weights = route(logits, K)
    xb = x.astype(jnp.bfloat16)
    gate = jnp.einsum("nd,edh->neh", xb, params["wg"])
    up = jnp.einsum("nd,edh->neh", xb, params["wu"])
    assert gate.dtype == jnp.bfloat16
    mix = np.zeros((rows, E), np.float32)
    mix[np.arange(rows)[:, None], np.asarray(experts)] = np.asarray(weights)
    hidden = ((jax.nn.relu(gate) * up).astype(jnp.float32)
              * mix[..., None]).astype(jnp.bfloat16)
    want = np.asarray(hidden, np.float64).reshape(rows, -1) \
        @ np.asarray(params["wd"], np.float64).reshape(E * 128, -1)
    close(got, want, 1e-5)
    rounded = np.asarray(jnp.asarray(want, jnp.bfloat16), np.float64)
    assert np.abs(rounded - want).max() > 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("router", ["uniform", "skewed"])
@pytest.mark.parametrize("rows", FEW)
def test_weights_built_by_comparison_equal_the_scattered_ones(rows, router):
    """`_few_rows` builds the top-k's weights [N, E] by comparing each
    chosen expert with 0..E-1 (a scatter is 192 serial updates on the
    chip, PERF.md PR 35); a row's k experts are distinct, so the result
    is the scattered form's bit for bit."""
    mod = layer()
    params, x, logits = draw(60 + rows, rows, router, "float32")
    experts, weights = route(logits, K)
    assert all(len(set(row)) == K for row in np.asarray(experts).tolist())
    mix = jnp.zeros((rows, E), jnp.float32).at[
        jnp.arange(rows)[:, None], experts].set(weights)
    gate = jnp.einsum("nd,edh->neh", x, params["wg"])
    up = jnp.einsum("nd,edh->neh", x, params["wu"])
    want = jnp.einsum("neh,ehd->nd", jax.nn.relu(gate) * up * mix[..., None],
                      params["wd"], preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(few_rows(mod, params, x, logits)), np.asarray(want))


# ------------------------- (d) no second copy of wd, and the named scopes
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_compiled_few_rows_holds_no_copy_of_wd_and_names_its_scopes(dtype):
    mod = RoutedExperts(256, 128, E, K)
    params, x, logits = draw(1, 6, "uniform", dtype, d=256, h=128)
    lowered = jax.jit(lambda p, a, b: mod.apply_routed(p, a, b)[0]).lower(
        params, x, logits)
    text = lowered.as_text(debug_info=True)
    for scope in ("moe experts", "moe gate up", "moe down"):
        assert scope in text, scope
    assert "ragged_dot" not in lowered.as_text()    # (no debug info)
    # (the CPU backend widens bf16 operands and plans its own scratch,
    # so the planned temporaries are the v5e compile's to bound:
    # tests/test_tpu_lowering.py::TestSparseDecodeProgramCompiles)
    compiled = lowered.compile()
    short = {"float32": "f32", "bfloat16": "bf16"}[dtype]
    shape = f"{short}[{E},128,256]"
    moved = [line for line in compiled.as_text().splitlines()
             if (" copy(" in line or " transpose(" in line)
             and line.split(" = ", 1)[-1].lstrip().startswith(shape)]
    assert not moved, moved


# ------------------------------------- (e) the path follows the shape alone
@pytest.mark.parametrize("rows,sorted_path",
                         [(1, True), (2, True), (3, False), (6, False),
                          (8, False), (9, True), (48, True)])
def test_path_is_chosen_by_shape_alone(rows, sorted_path):
    mod = layer()
    params, x, logits = draw(0, rows, "uniform", "float32")
    assert uses_ragged_dot(mod, params, x, logits) is sorted_path
    # and by nothing the router says
    _, _, skewed = draw(0, rows, "skewed", "float32")
    assert uses_ragged_dot(mod, params, x, skewed) is sorted_path
