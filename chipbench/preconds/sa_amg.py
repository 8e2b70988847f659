"""sa_amg: the program's smoothed-aggregation multigrid
(``make_preconditioner(A, "amg")``, one V(1,1)-cycle a apply), and as its
reference a plain float64 hierarchy and V-cycle written here.

The reference follows the method as published (Vaněk, Mandel and Brezina,
1996) with the configuration's options: strength ``|a_ij| >= theta
sqrt(|a_ii a_jj|)`` off the diagonal; greedy aggregation in row order (seed
a row whose strong neighbours are all free, with them; attach a leftover to
its first strongly connected aggregate; make what remains a singleton);
tentative prolongator ``T`` (one unit a row); ``P = (I - omega D^-1 A) T``;
``R = P^T``; Galerkin ``A_c = R A P``; the descent ends at ``coarse_size``
rows, at ``max_levels`` (10, the program's documented default, unless the
options name it), or at a level whose aggregation would keep more than half
of its rows; the coarsest level is inverted densely.  The cycle from a zero
guess: ``x = omega D^-1 r``, restrict ``R (r - A x)``, recurse, prolong,
one more weighted-Jacobi sweep.  Sparse products are expansions coalesced by
``numpy``.  Nothing here imports the program.

The control casts the operand to one dtype, so :func:`reference_operand`
returns every level's values as one array and keeps the integer structure
in :data:`_STRUCTURE`, keyed by that array's length; the structure enters a
jitted cycle as constants, so the cycle applies the transfers in their
factored form and keeps only each operator's columns and aggregates.
"""

import numpy as np

#: the program's default depth limit, when the options name none
MAX_LEVELS = 10

#: value-array length -> the hierarchy's structure (see reference_operand)
_STRUCTURE = {}


def generate(A, opts: dict, executor):
    from repro.precond import make_preconditioner

    return make_preconditioner(A, "amg", executor=executor, **opts)


def operand_bytes(n: int, opts: dict, itemsize: int) -> int:
    raise ValueError("a multigrid hierarchy's bytes are not a function of the "
                     "row count: they come from the program's level counters "
                     "(amg_level_nnz, amg_transfer_nnz, amg_level_rows)")


# -- sparse matrices as (indptr, indices, values, shape) --------------------------
def coalesce(rows, cols, vals, shape):
    """CSR of the triplets, duplicates summed, columns ascending."""
    m, n = shape
    key = rows.astype(np.int64) * n + cols.astype(np.int64)
    uniq, inv = np.unique(key, return_inverse=True)
    values = np.bincount(inv, weights=vals, minlength=uniq.size)
    indptr = np.zeros(m + 1, np.int64)
    np.cumsum(np.bincount(uniq // n, minlength=m), out=indptr[1:])
    return indptr, uniq % n, values, (m, n)


def row_ids(mat):
    indptr = mat[0]
    return np.repeat(np.arange(indptr.size - 1), np.diff(indptr))


def matmul(a, b):
    """``a @ b``: each entry ``a_ik`` times each entry of ``b``'s row k."""
    a_rows, a_cols, a_vals = row_ids(a), a[1], a[2]
    b_indptr, b_cols, b_vals = b[0], b[1], b[2]
    count = np.diff(b_indptr)[a_cols]
    src = np.repeat(np.arange(a_cols.size), count)
    first = np.cumsum(count) - count
    pos = b_indptr[a_cols][src] + np.arange(src.size) - first[src]
    return coalesce(a_rows[src], b_cols[pos], a_vals[src] * b_vals[pos],
                    (a[3][0], b[3][1]))


def transpose(a):
    return coalesce(a[1], row_ids(a), a[2], (a[3][1], a[3][0]))


def diagonal(a):
    rows = row_ids(a)
    d = np.zeros(a[3][0])
    on = rows == a[1]
    d[rows[on]] = a[2][on]
    return d


# -- the hierarchy ------------------------------------------------------------------
def strong(a, theta: float) -> np.ndarray:
    rows, cols, vals = row_ids(a), a[1], a[2]
    d = np.abs(diagonal(a))
    return (rows != cols) & (np.abs(vals) >= theta * np.sqrt(d[rows] * d[cols]))


def aggregates(a, is_strong):
    """``(agg, n_agg)``: greedy aggregation in row order."""
    n = a[3][0]
    indptr, cols = a[0].tolist(), a[1].tolist()
    s = is_strong.tolist()
    nbrs = [[cols[t] for t in range(indptr[i], indptr[i + 1]) if s[t]]
            for i in range(n)]
    agg = [-1] * n
    count = 0
    for i in range(n):
        if agg[i] == -1 and all(agg[j] == -1 for j in nbrs[i]):
            for j in [i] + nbrs[i]:
                agg[j] = count
            count += 1
    for i in range(n):
        if agg[i] == -1:
            for j in nbrs[i]:
                if agg[j] != -1:
                    agg[i] = agg[j]
                    break
    for i in range(n):
        if agg[i] == -1:
            agg[i] = count
            count += 1
    return np.asarray(agg), count


def hierarchy(a, opts: dict):
    """``(levels, coarse)``: each level ``{"A", "P", "R", "inv_diag"}`` in
    float64, and the coarsest operator."""
    for key, want in (("cycle", "v"), ("smoother", "jacobi"),
                      ("coarse_solver", "dense"), ("smooth_prolongator", True)):
        if opts.get(key, want) != want:
            raise ValueError(f"the reference implements {key}={want!r} only")
    theta, omega = float(opts["theta"]), float(opts["omega"])
    levels = []
    while (a[3][0] > int(opts["coarse_size"])
           and len(levels) < int(opts.get("max_levels", MAX_LEVELS))):
        n = a[3][0]
        agg, n_agg = aggregates(a, strong(a, theta))
        if 2 * n_agg > n:
            break
        inv_diag = 1.0 / diagonal(a)
        rows = row_ids(a)
        # P = T - omega D^-1 A T: row i holds 1 at agg[i], and -omega a_ij / a_ii
        # at agg[j] for every entry a_ij
        p = coalesce(np.concatenate([np.arange(n), rows]),
                     np.concatenate([agg, agg[a[1]]]),
                     np.concatenate([np.ones(n), -omega * inv_diag[rows] * a[2]]),
                     (n, n_agg))
        r = transpose(p)
        levels.append({"A": a, "P": p, "R": r, "inv_diag": inv_diag, "agg": agg})
        a = matmul(r, matmul(a, p))
    return levels, a


def ell(mat):
    """``(cols, slots)``: ``mat`` row by row in ``w`` slots, where ``w`` is
    its widest row; ``slots`` are the positions of its entries in the
    row-major ``(rows, w)`` layout (padding: column 0, value 0)."""
    m = mat[3][0]
    count = np.diff(mat[0])
    w = max(int(count.max()), 1)
    filled = np.arange(w) < count[:, None]
    cols = np.zeros((m, w), np.int32)
    cols[filled] = mat[1]
    return cols, np.flatnonzero(filled)


def reference_operand(system, values, opts: dict) -> np.ndarray:
    """One float64 array: for each level its operator's values in the slots
    of :func:`ell`, then its inverse diagonal; last the coarsest level's
    dense inverse.  The cycle applies ``P = (I - omega D^-1 A) T`` and
    ``R = P^T`` in that factored form (``T x_c = x_c[agg]``), so a level
    keeps its operator's columns and its aggregates; the stored entries of
    ``P`` and ``R`` are counted in the structure."""
    a = (np.asarray(system.indptr, np.int64), np.asarray(system.indices, np.int64),
         np.asarray(values, np.float64), (system.n, system.n))
    levels, coarse = hierarchy(a, opts)
    n_c = coarse[3][0]
    dense = np.zeros((n_c, n_c))
    dense[row_ids(coarse), coarse[1]] = coarse[2]
    parts, layout, offset = [], [], 0
    for level in levels:
        cols, slots = ell(level["A"])
        padded = np.zeros(cols.size)
        padded[slots] = level["A"][2]
        layout.append({"cols": cols, "agg": level["agg"].astype(np.int32), "n_agg": level["P"][3][1],
                       "values": offset, "inv_diag": offset + padded.size,
                       "nnz": level["A"][1].size, "transfer_nnz": 2 * level["P"][1].size})
        parts += [padded, level["inv_diag"]]
        offset += padded.size + level["inv_diag"].size
    parts.append(np.linalg.inv(dense).reshape(-1))
    flat = np.concatenate(parts)
    _STRUCTURE[flat.size] = {"levels": layout, "coarse": (n_c, offset),
                             "coarse_nnz": coarse[1].size}
    return flat


def levels_of(t) -> list:
    """``[(rows, nnz, transfer_nnz)]`` of each level, then ``(rows, nnz,
    None)`` of the coarsest, of an operand :func:`reference_operand` made."""
    s = _STRUCTURE[t.shape[0]]
    return ([(lv["cols"].shape[0], lv["nnz"], lv["transfer_nnz"]) for lv in s["levels"]]
            + [(s["coarse"][0], s["coarse_nnz"], None)])


def reference_apply(t, v, opts: dict):
    """One V(1,1)-cycle from a zero guess, in ``t``'s dtype."""
    import jax
    import jax.numpy as jnp

    s = _STRUCTURE[t.shape[0]]
    omega = jnp.asarray(float(opts["omega"]), t.dtype)

    def cycle(k, r):
        if k == len(s["levels"]):
            n_c, off = s["coarse"]
            inv = jax.lax.dynamic_slice_in_dim(t, off, n_c * n_c).reshape(n_c, n_c)
            return jnp.dot(inv, r, precision=jax.lax.Precision.HIGHEST)
        lv = s["levels"][k]
        cols, agg = lv["cols"], lv["agg"]
        vals = jax.lax.dynamic_slice_in_dim(t, lv["values"], cols.size).reshape(cols.shape)
        d = jax.lax.dynamic_slice_in_dim(t, lv["inv_diag"], r.shape[0])

        def a(x):
            return (vals * x[cols]).sum(axis=1)

        x = omega * d * r
        res = r - a(x)
        rc = jax.ops.segment_sum(res - omega * a(d * res), agg, num_segments=lv["n_agg"])
        xc = cycle(k + 1, rc)
        fine = xc[agg]
        x = x + fine - omega * d * a(fine)
        return x + omega * d * (r - a(x))

    return cycle(0, v.astype(t.dtype))
