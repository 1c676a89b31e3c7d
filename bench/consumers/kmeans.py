"""Consumer kind ``kmeans``: ``SparsifiedKMeans(k, algorithm, n_init)``.

The reference is minibatch K-means on the sketch: K-means++ seeding on the
first chunk, once per hypothesis, then per chunk an assignment under the
step-start centers and each touched coordinate moved to its running mean.
Compared as ``km.*``: the centers, the objectives and the final centers in
the original domain (``out``) by relative gaps, and the row count.
"""
import jax.numpy as jnp
import numpy as np

from bench import compare as C
from bench import reference as R

PREFIX = "km"


def build(api, plan, c: dict, key):
    return api.SparsifiedKMeans(int(c["k"]), plan, key=key, algorithm=c["algorithm"],
                                n_init=int(c.get("n_init", 3)))


def state(est):
    return est._km_state


def extract(est) -> dict:
    st = est._km_state
    return dict(centers=np.asarray(st.centers), obj=np.asarray(st.obj), count=int(st.count),
                out=np.asarray(est.centers_))


def ref_init(ref, c: dict):
    return None          # seeded from the first chunk


def ref_fold(ref, c: dict, st, vals, idx):
    if st is None:
        k, r = int(c["k"]), int(c.get("n_init", 3))
        centers = R.kmeans_seed(ref.keys.kmeans(), vals, idx, k, r, ref.p_pad)
        st = [centers, jnp.zeros(centers.shape, jnp.int32), jnp.zeros((r,), jnp.float32)]
    return list(R.kmeans_fold(*st, vals, idx, ref.fold))


def ref_finalize(ref, c: dict, st, rows: int) -> dict:
    st = [np.asarray(a) for a in st]
    best = int(np.argmin(st[2]))
    return dict(centers=st[0], obj=st[2], count=rows,
                out=R.unmix(st[0][best], ref.signs_np, ref.p))


def readings(got: dict, want: dict) -> dict:
    out = {name: C.rel(got[name], want[name]) for name in ("centers", "obj", "out")}
    out["count"] = float(abs(got["count"] - want["count"]))
    return out


def fold_work(c: dict, shape: dict) -> tuple:
    """(operations, bytes) of one chunk's minibatch step over r hypotheses
    of K centers, from shapes: centers (f32) and counts (int32) read and
    written, 2·r·K·p_pad·8 bytes; 2·3·r·n·m·K operations (two assignment
    passes, the fold's and the reassignment count's, each a subtract, a
    square and an add per kept coordinate and center)."""
    n, pp, m = shape["n"], shape["p_pad"], shape["m"]
    r, k = int(c.get("n_init", 3)), int(c["k"])
    return 2 * 3.0 * r * n * m * k, 2.0 * r * k * pp * 8
