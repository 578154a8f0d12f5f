"""Sparse-eligibility bucket layout (the scale layer's data structure).

The paper's defining premise is that "certain users' tasks may only be
serviced by a subset of the servers" (Section II) — yet the dense solvers
carry (N, K) arrays and refill every server against every user each round,
so per-round cost is O(N*K*R) no matter how sparse eligibility is. At
cell-structured datacenter scale realistic density is a few percent:
``BucketedLayout`` stores, per server, just the users eligible on it, so
fills and row-sum maintenance scale with nnz(eligibility) instead of N*K.

One structure serves both backends:

* numpy — ``bucket_users(i)`` returns server i's user-index list (CSR-style
  ragged rows); ``user_ptr``/``user_servers`` is the transposed (CSC-style)
  adjacency the active-set sweep uses to mark which servers a changed user
  ripples to.
* jax — ``indices``/``mask`` are padded ``(K, Bmax)`` int32/bool arrays
  (every row is a permutation prefix, so indices within a row are distinct
  — gathers and scatter-adds never collide per server). Padded slots carry
  ``mask == False`` and gamma 0 in the gathered buckets, so padding is
  exactly inert in the fill — the same trick ``psdsf_jax.batch_problems``
  uses for heterogeneous batch sizes. ``colours`` is a ``(C, W)`` int32
  greedy colouring of the servers' conflict graph (two servers conflict
  when they share a user): the jitted Gauss-Seidel round fills each class
  at once, C serial steps instead of K.

Builders: ``from_support`` (any (N, K) boolean support),
``from_problem`` (eligibility/gamma > 0) and ``from_cluster``
(``sched.cluster.Cluster`` + jobs). ``resolve_layout`` maps the public
``layout="auto"`` knob to "dense"/"bucketed" by a density threshold.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from .types import AllocationProblem

#: public layout axis accepted by the solvers ("auto" resolves by density)
LAYOUTS = ("dense", "bucketed", "auto")

#: ``layout="auto"`` picks the bucketed path below this eligibility density
AUTO_DENSITY_MAX = 0.25

#: ...but only once the instance is big enough for gather/scatter overhead
#: to pay for itself (tiny paper instances always resolve dense)
AUTO_MIN_USERS = 64
AUTO_MIN_SERVERS = 8


@dataclasses.dataclass(frozen=True)
class BucketedLayout:
    """Per-server user buckets of one eligibility support (see module doc).

    ``indices[i, :counts[i]]`` are the users eligible on server i (sorted
    ascending); ``indices[i, counts[i]:]`` is padding (arbitrary distinct
    user ids with ``mask`` False). ``user_ptr``/``user_servers`` is the
    user -> servers adjacency in CSR-over-users form: user n's servers are
    ``user_servers[user_ptr[n]:user_ptr[n + 1]]``. ``colours[c]`` is the
    c-th class of servers that pairwise share no user (ascending ids,
    padded with the sentinel K); every server is in exactly one class.
    """

    indices: np.ndarray       # (K, Bmax) int32
    mask: np.ndarray          # (K, Bmax) bool
    counts: np.ndarray        # (K,) int32
    num_users: int
    user_ptr: np.ndarray      # (N + 1,) int64
    user_servers: np.ndarray  # (nnz,) int32
    colours: np.ndarray       # (C, W) int32, padded with K

    # -- construction --------------------------------------------------------
    @classmethod
    def from_support(cls, support: np.ndarray) -> "BucketedLayout":
        """Build from an (N, K) boolean/0-1 support matrix."""
        supp = np.asarray(support) > 0
        if supp.ndim != 2:
            raise ValueError(f"support must be (N, K): {supp.shape}")
        n, k = supp.shape
        counts = supp.sum(axis=0).astype(np.int32)
        bmax = max(int(counts.max(initial=0)), 1)
        # stable argsort of ~support per column: each row of `indices` is a
        # prefix of a permutation of 0..N-1 — eligible users first (in
        # ascending order), so padded slots still hold DISTINCT user ids and
        # per-server gathers/scatters never collide
        order = np.argsort(~supp, axis=0, kind="stable")      # (N, K)
        indices = np.ascontiguousarray(order[:bmax].T).astype(np.int32)
        mask = np.ascontiguousarray(
            np.take_along_axis(supp, order[:bmax], axis=0).T)
        # CSC side: user -> servers, vectorized via one stable sort of the
        # nnz coordinate list by user id
        srv_of, usr_of = np.nonzero(supp.T)                   # row-major in i
        perm = np.argsort(usr_of, kind="stable")
        user_servers = srv_of[perm].astype(np.int32)
        user_ptr = np.searchsorted(usr_of[perm], np.arange(n + 1))
        user_ptr = user_ptr.astype(np.int64)
        return cls(indices=indices, mask=mask, counts=counts, num_users=n,
                   user_ptr=user_ptr, user_servers=user_servers,
                   colours=_greedy_colours(indices, counts, user_ptr,
                                           user_servers))

    @classmethod
    def from_problem(cls, problem: AllocationProblem,
                     gamma: Optional[np.ndarray] = None) -> "BucketedLayout":
        """Build from a problem's eligibility (or an explicit gamma/level-
        rate matrix — its support coincides with eligibility for every
        mechanism; see ``placement.solve_with_placement``)."""
        supp = problem.eligibility if gamma is None else gamma
        return cls.from_support(np.asarray(supp) > 0)

    @classmethod
    def from_cluster(cls, cluster, jobs: Sequence) -> "BucketedLayout":
        """Build from a ``sched.cluster.Cluster`` and its jobs — the layout
        of ``cluster.problem(jobs)`` (generation/topology eligibility)."""
        return cls.from_problem(cluster.problem(jobs))

    # -- shape/statistics ----------------------------------------------------
    @property
    def num_servers(self) -> int:
        """K, the number of server buckets."""
        return int(self.indices.shape[0])

    @property
    def bucket_max(self) -> int:
        """Bmax, the padded bucket width (largest per-server user count)."""
        return int(self.indices.shape[1])

    @property
    def nnz(self) -> int:
        """Number of (user, server) eligibility pairs."""
        return int(self.counts.sum())

    @property
    def density(self) -> float:
        """nnz / (N * K); 0.0 for a degenerate empty support."""
        cells = self.num_users * self.num_servers
        return self.nnz / cells if cells else 0.0

    # -- numpy access --------------------------------------------------------
    def bucket_users(self, i: int) -> np.ndarray:
        """Server i's user-index list (ascending, no padding)."""
        return self.indices[i, :int(self.counts[i])]

    def bucket_lists(self) -> List[np.ndarray]:
        """All per-server user-index lists (views into ``indices``)."""
        return [self.bucket_users(i) for i in range(self.num_servers)]

    def servers_of(self, users: np.ndarray) -> np.ndarray:
        """Concatenated server lists of ``users`` (with duplicates) — the
        ripple set the active-set sweep marks dirty when those users'
        allocations change. Vectorized ragged gather over the CSC side."""
        return _servers_of(self.user_ptr, self.user_servers, users)

    # -- dense <-> bucketed transport ---------------------------------------
    def gather(self, x: np.ndarray) -> np.ndarray:
        """Dense (N, K) -> padded (K, Bmax) buckets (padding zeroed)."""
        xb = np.take_along_axis(np.asarray(x).T, self.indices, axis=1)
        return np.where(self.mask, xb, 0.0)

    def scatter(self, xb: np.ndarray) -> np.ndarray:
        """Padded (K, Bmax) buckets -> dense (N, K) (padding dropped)."""
        x = np.zeros((self.num_users, self.num_servers),
                     dtype=np.asarray(xb).dtype)
        cols = np.broadcast_to(
            np.arange(self.num_servers)[:, None], self.indices.shape)
        x[self.indices[self.mask], cols[self.mask]] = np.asarray(xb)[self.mask]
        return x


def _servers_of(user_ptr: np.ndarray, user_servers: np.ndarray,
                users: np.ndarray) -> np.ndarray:
    """``BucketedLayout.servers_of`` on the bare CSC arrays."""
    users = np.asarray(users, dtype=np.int64)
    lens = user_ptr[users + 1] - user_ptr[users]
    total = int(lens.sum())
    if total == 0:
        return user_servers[:0]
    starts = user_ptr[users]
    offs = np.repeat(starts - np.insert(np.cumsum(lens)[:-1], 0, 0), lens)
    return user_servers[offs + np.arange(total)]


def _greedy_colours(indices: np.ndarray, counts: np.ndarray,
                    user_ptr: np.ndarray,
                    user_servers: np.ndarray) -> np.ndarray:
    """First-fit colouring of the server conflict graph in id order: each
    server takes the smallest class that none of its neighbours (the
    servers of its users) holds yet. Returns the classes as a (C, W)
    int32 array of ascending server ids, padded with the sentinel K. No
    K x K matrix is built: a server's neighbours come from the CSC side."""
    k = indices.shape[0]
    colour = np.full(k, -1, np.int64)
    for i in range(k):
        taken = colour[_servers_of(user_ptr, user_servers,
                                   indices[i, :counts[i]])]
        free = np.ones(taken.size + 1, bool)
        free[taken[(taken >= 0) & (taken <= taken.size)]] = False
        colour[i] = int(np.argmax(free))
    sizes = np.bincount(colour, minlength=1)
    out = np.full((sizes.size, max(int(sizes.max()), 1)), k, np.int32)
    order = np.argsort(colour, kind="stable")
    slot = np.arange(k) - np.repeat(np.cumsum(sizes) - sizes, sizes)
    out[colour[order], slot] = order
    return out


def resolve_layout(layout: str, problem: Optional[AllocationProblem] = None,
                   support: Optional[np.ndarray] = None) -> str:
    """Map the public ``layout`` knob to a concrete "dense"/"bucketed".

    ``"auto"`` picks "bucketed" when the eligibility density is below
    ``AUTO_DENSITY_MAX`` AND the instance is at least ``AUTO_MIN_USERS`` x
    ``AUTO_MIN_SERVERS`` (gather/scatter bookkeeping never pays off on the
    paper's toy instances); unknown names raise.
    """
    if layout not in LAYOUTS:
        raise ValueError(f"layout must be one of {LAYOUTS}: {layout!r}")
    if layout != "auto":
        return layout
    supp = (np.asarray(support) > 0 if support is not None
            else np.asarray(problem.eligibility) > 0)
    n, k = supp.shape
    if n < AUTO_MIN_USERS or k < AUTO_MIN_SERVERS:
        return "dense"
    density = supp.mean() if supp.size else 0.0
    return "bucketed" if density <= AUTO_DENSITY_MAX else "dense"
