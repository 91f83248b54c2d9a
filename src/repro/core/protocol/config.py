"""CPMLConfig: all static parameters of one CodedPrivateML deployment.

The config is a frozen (hashable) dataclass so it can ride through
`jax.jit(static_argnums=...)` — every downstream stage (encode / compute /
decode / engine) specializes on it at trace time.

Beyond the paper's (N, K, T, r) and quantization scales this adds:
  * ``c``          — number of one-vs-all logistic heads (1 = the paper's
                     binary task).  All heads share the SAME coded dataset
                     shares, so encoding cost is amortized c-ways.
  * ``batch_rows`` — mini-batch SGD: rows-per-part drawn each round from the
                     once-encoded shares (row selection commutes with
                     Lagrange encoding, DESIGN.md §6).  None = full batch.
"""
from __future__ import annotations

import dataclasses

from repro.core import field, lagrange, sigmoid_poly


@dataclasses.dataclass(frozen=True)
class CPMLConfig:
    N: int                  # workers
    K: int                  # parallelization (dataset split)
    T: int                  # privacy threshold
    r: int = 1              # sigmoid polynomial degree
    c: int = 1              # one-vs-all heads (1 = binary logistic regression)
    lx: int = 2             # dataset quantization scale (paper §5)
    lw: int = 4             # weight quantization scale (paper §5)
    lc: int = 6             # sigmoid-coefficient scale (see sigmoid_poly.py)
    p: int = field.P
    backend: str = "vmap"   # "vmap" | "shard"
    mesh_axis: str = "workers"
    batch_rows: int | None = None   # rows per part per round (None = full)

    def __post_init__(self):
        need = lagrange.recovery_threshold(self.K, self.T, self.r)
        assert self.N >= need, (
            f"N={self.N} < recovery threshold {need} for "
            f"(K={self.K}, T={self.T}, r={self.r}); Theorem 1 violated")
        assert self.c >= 1
        assert self.batch_rows is None or self.batch_rows >= 1

    @property
    def threshold(self) -> int:
        return lagrange.recovery_threshold(self.K, self.T, self.r)

    @property
    def scheme(self) -> lagrange.CodingScheme:
        return lagrange.CodingScheme(self.N, self.K, self.T, self.p)

    @property
    def grad_scale(self) -> int:
        return sigmoid_poly.gradient_scale_poly(self.lx, self.lw, self.r,
                                                self.lc)

    def headroom_bits(self, x_max: float, m: int) -> float:
        """log2((p-1)/2) - log2(worst-case decoded magnitude).

        Negative => the decoded sub-gradient h(beta_k) can wrap around
        (paper §3.1's overflow error).  Worst case per part: sum over m/K
        samples of x̄ * ḡ at the aligned scale.  Use P30 / smaller lc / larger
        K when this goes negative (r=2 at the paper's 24-bit prime does).
        Mini-batching HELPS here: only batch_rows samples accumulate.
        """
        import math
        rows = m / self.K if self.batch_rows is None else self.batch_rows
        per_part = rows * (2 ** self.lx * max(x_max, 1e-9)) \
            * 2 ** (self.lc + self.r * (self.lx + self.lw))
        return math.log2((self.p - 1) / 2) - math.log2(per_part)
