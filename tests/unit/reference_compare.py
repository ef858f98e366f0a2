"""The program against its plain reference, compiled once: seeded weights,
one forward, the gradients, and the leaf-by-leaf comparison that ten model
files copied.  The program's side runs under ONE ``jax.jit``: bare, every
primitive of the forward and of the backward is compiled on its own, 2.5 x
the seconds (PR 63, Xing's 5 blocks: init 16.5 -> 6.6 s, gradients 42.0 ->
15.7 s).  A reference's ``jax.numpy`` lines are cheap op by op (10.5 s bare,
12.8 s compiled there): its side stays bare in the file that owns it, which
also keeps its own tolerance and holds what it computes in a module-scoped
fixture, so that two tests never pay for one program twice.
"""
import jax
import numpy as np
from flax.core import meta


def init(model, *args, seed=0, scale=None, **kwargs):
    """Unboxed leaves of ONE compiled ``model.init``, the matrices times
    ``scale`` (attention not near-uniform, a router's choices no near-ties).
    Not bit for bit the bare call's: a test that pins a digest or a literal
    to bare-initialised values calls ``model.init`` itself."""
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(seed), *args,
                                            **kwargs)["params"])
    return params if scale is None else jax.tree_util.tree_map(
        lambda a: a * scale if a.ndim >= 2 else a, params)


def apply(model, params, *args, **kwargs):
    """``model.apply`` (or a layer's) as one compiled program.  Arrays among
    the arguments are traced: what selects a branch belongs in the config."""
    def run(p, a, kw):
        out = model.apply({"params": p}, *a, **kw)
        return dict(out) if isinstance(out, dict) else out  # no ModelOutput
    return jax.jit(run)(params, args, kwargs)


def forward_and_gradients(program, params):
    """``(out, grads)`` of ``program(params) -> out``, a dict with ``"loss"``:
    the forward's every output and the gradient of its loss, ONE program."""
    def loss(p):
        out = dict(program(p))      # ``ModelOutput`` is no pytree
        return out["loss"], out

    (_, out), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    return out, grads


def rel(a, b):
    """``|a - b| / |b|`` over all entries, in float64."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare_leaves(got, want, *, tol, measure, no_gradient=(),
                   vanishing=None):
    """Every leaf of ``want`` against the leaf of ``got`` at its path; the
    two trees must hold the same paths, and at least one.  ``measure``:

    - ``"max"``: ``|g - w| <= tol * max|w|`` entry by entry (``atol`` of
      ``numpy.testing.assert_allclose``, its default ``rtol`` with it), and
      ``max|w| > 0``;
    - ``"norm"``: ``|g - w| / |w| < tol`` in float64, and ``|w| > 0``.

    A leaf whose path names one of ``no_gradient`` (a router's selection
    bias: it picks, never weighs) must be zero on both sides.  ``vanishing
    = (below, within)``, under ``"norm"``: a leaf whose reference has a norm
    under ``below`` is held to ``|g - w| < within`` instead, and the caller
    says which leaves may be such.  Returns ``(paths, small)``: the paths
    compared under the tolerance, in order, and ``{name: |g - w|}`` of the
    vanishing ones."""
    assert measure in ("max", "norm"), measure
    flat_got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    flat_want = jax.tree_util.tree_flatten_with_path(want)[0]
    assert flat_want, "the reference holds no leaf"
    assert set(flat_got) == {path for path, _ in flat_want}, (
        "the trees differ in their paths",
        sorted(jax.tree_util.keystr(p) for p in
               set(flat_got) ^ {path for path, _ in flat_want}))
    paths, small = [], {}
    for path, w in flat_want:
        name = jax.tree_util.keystr(path)
        g = flat_got[path]
        assert np.shape(g) == np.shape(w), name
        if any(leaf in name for leaf in no_gradient):
            assert not np.any(g) and not np.any(w), name    # no gradient
            continue
        if measure == "max":
            assert float(np.abs(w).max()) > 0, name
            np.testing.assert_allclose(
                g, w, atol=tol * float(np.abs(w).max()), err_msg=name)
        elif vanishing and np.linalg.norm(w) < vanishing[0]:
            small[name] = float(np.linalg.norm(np.asarray(g) - np.asarray(w)))
            assert small[name] < vanishing[1], (name, small[name])
            continue
        else:
            assert np.linalg.norm(w) > 0, name
            assert rel(g, w) < tol, (name, rel(g, w))
        paths.append(path)
    assert paths, "every leaf was set aside: nothing was compared"
    return paths, small
