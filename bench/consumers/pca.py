"""Consumer kind ``pca``: ``SparsifiedPCA(n_components)``.

On the plan's ``cov_path``: ``lowrank`` folds the O(p·l) range state
(Y += Wᵀ(W·Ω), diag, sum_w) and finalizes it through the top l/2 left
singular vectors; ``dense`` folds the (p_pad, p_pad) second moment WᵀW and
sum_w and takes the top eigenpairs of the Thm-6 estimate. Compared as
``pca.*``: the folded state's relative Frobenius gaps, the row count, the
eigenvalues' relative gap (``evals``) and the sine of the largest principal
angle between the top components (``subspace``).
"""
import jax.numpy as jnp
import numpy as np

from bench import compare as C
from bench import reference as R

PREFIX = "pca"


def build(api, plan, c: dict, key):
    return api.SparsifiedPCA(int(c["n_components"]), plan, key=key)


def state(est):
    return est._reducer.state if est._reducer is not None else None


def extract(est) -> dict:
    st = est._reducer.state
    out = {}
    if hasattr(st, "y"):
        out.update(y=np.asarray(st.y), diag=np.asarray(st.diag), sum_w=np.asarray(st.sum_w))
    else:
        out.update(cov=np.asarray(st.sum_wwt), sum_w=np.asarray(st.sum_w))
    out.update(count=int(st.count), evals=np.asarray(est.explained_variance_),
               comps=np.asarray(est.components_))
    return out


def _lowrank(ref) -> bool:
    return ref.plan.get("cov_path") == "lowrank"


def ref_init(ref, c: dict):
    pp = ref.p_pad
    if _lowrank(ref):
        ell = int(ref.plan["rank"])
        return [jnp.zeros((pp, ell), jnp.float32), jnp.zeros((pp,), jnp.float32),
                jnp.zeros((pp,), jnp.float32)]
    return [jnp.zeros((pp, pp), jnp.float32), jnp.zeros((pp,), jnp.float32)]


def ref_fold(ref, c: dict, st, vals, idx):
    if _lowrank(ref):
        return list(R.range_fold(*st, vals, idx, ref.omega, ref.fold))
    return list(R.moment_fold(*st, vals, idx, ref.p_pad, ref.fold))


def ref_finalize(ref, c: dict, st, rows: int) -> dict:
    st = [np.asarray(a) for a in st]
    k = int(c["n_components"])
    if _lowrank(ref):
        ev, comps = R.range_finalize(st[0], st[1], rows, np.asarray(ref.omega), ref.m, k,
                                     ref.fold if ref.control else None)
        return dict(y=st[0], diag=st[1], sum_w=st[2], count=rows, evals=ev,
                    comps=R.unmix(comps, ref.signs_np, ref.p))
    ev, comps = R.moment_finalize(st[0], rows, ref.m, k)
    return dict(cov=st[0], sum_w=st[1], count=rows, evals=ev,
                comps=R.unmix(comps, ref.signs_np, ref.p))


def readings(got: dict, want: dict) -> dict:
    out = {name: C.rel(got[name], want[name])
           for name in ("y", "diag", "sum_w", "cov") if name in want}
    out["count"] = float(abs(got["count"] - want["count"]))
    out["evals"] = C.rel(got["evals"], want["evals"])
    out["subspace"] = C.subspace(got["comps"], want["comps"])
    return out


def fold_work(c: dict, shape: dict) -> tuple:
    """(operations, bytes) of folding one chunk's sketch, from shapes:
    low-rank, Ω read (p_pad·l·4) and Y read and written (2·p_pad·l·4)
    bytes and 4·n·m·l operations (T = W·Ω and Y += Wᵀ·T, each n·m·l
    multiply-adds); dense, the (p_pad, p_pad) f32 state read and written
    (2·p_pad²·4) bytes and 2·n·m² operations (each row's m² outer product,
    multiply and add)."""
    n, pp, m = shape["n"], shape["p_pad"], shape["m"]
    if shape.get("l"):
        ell = shape["l"]
        return 4.0 * n * m * ell, 3.0 * pp * ell * 4
    return 2.0 * n * m * m, 2.0 * pp * pp * 4
