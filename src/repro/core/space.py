"""Parameter spaces for input-aware auto-tuning (paper §3).

The paper distinguishes the space of *possible* configurations X-hat (every
combination of per-parameter choices) from the space of *legal* configurations
X (those that compile and run within hardware resource limits).  For GEMM the
paper has 10 tuning + 6 input parameters; our TPU adaptation has 8 tuning + 6
input parameters (see DESIGN.md §3 for the PTX->Pallas mapping).

A :class:`ParamSpace` is a small declarative object: an ordered mapping of
parameter name -> tuple of admissible values, plus a legality predicate over a
fully instantiated configuration.  Everything downstream (the generative
sampler, the featurizer, the exhaustive runtime search) is generic over a
ParamSpace - this genericity is the "more flexible front-end" the paper lists
as future work (§9).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import (Callable, Dict, Iterable, List, Mapping, Optional,
                    Tuple)

# ---------------------------------------------------------------------------
# Hardware constants for legality checks (TPU v5e target; see DESIGN.md §2).
# ---------------------------------------------------------------------------
# Scoped VMEM one kernel may use: every pallas_call is compiled with this as
# its vmem_limit_bytes (kernels/common.py), and a config is legal only if
# its *_vmem_bytes count fits in it.  v5e has 128 MiB of VMEM per core.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
# Mosaic's own scratch inside that limit (80 KiB measured for the largest
# GEMM block), charged to every count with margin
MOSAIC_SCRATCH_BYTES = 512 * 1024
SUBLANE = 8                             # fp32 sublane tile
LANE = 128                              # lane tile
MXU = 128                               # systolic array dimension

Config = Dict[str, int]


@dataclasses.dataclass(frozen=True)
class ParamSpace:
    """Declarative tuning-parameter space with a legality predicate."""

    name: str
    params: Mapping[str, Tuple[int, ...]]            # tuning parameters
    input_params: Tuple[str, ...]                    # names of input features
    is_legal: Callable[[Mapping[str, int], Mapping[str, int]], bool]

    @property
    def param_names(self) -> Tuple[str, ...]:
        return tuple(self.params.keys())

    def cardinality(self) -> int:
        n = 1
        for v in self.params.values():
            n *= len(v)
        return n

    def enumerate(self) -> Iterable[Config]:
        """Yield every configuration in X-hat (legal or not)."""
        names = self.param_names
        for combo in itertools.product(*(self.params[n] for n in names)):
            yield dict(zip(names, combo))

    def enumerate_legal(self, inputs: Mapping[str, int]) -> List[Config]:
        """Materialize X for a fixed input (used by runtime inference, §6)."""
        return [c for c in self.enumerate() if self.is_legal(c, inputs)]

    def contains(self, cfg: Mapping[str, int]) -> bool:
        return all(cfg.get(k) in v for k, v in self.params.items())


# ---------------------------------------------------------------------------
# GEMM: C[M, N] = A[M, K] @ B[K, N]
#
# Tuning parameters (TPU adaptation of the paper's {M_S,N_S,M_L,N_L,U,K_S,K_L,K_G}):
#   bm, bn      VMEM output-block shape            (paper: M_L x N_L)
#   bk          K-extent of A/B slabs per grid step (paper: U, prefetch width)
#   k_unroll    in-kernel unroll of the bk loop     (paper: K_S)
#   k_split     parallel split-K partial outputs    (paper: K_G; no atomics on
#               TPU so partials are materialized and reduced - pays the same
#               "diminished write bandwidth" cost the paper describes)
#   order       grid iteration order (0: m-major, 1: n-major) - HBM reuse
#   acc32       accumulate in fp32 (1) or io dtype (0)
#   prefetch    DMA pipeline depth (1 = no double buffering)
#
# Input parameters: M, N, K, dtype_bits, trans_a, trans_b.
# The sequential K revisits of one output block (paper's K_L) are derived:
# k_grid = ceil(K / (k_split * bk)).
# ---------------------------------------------------------------------------

GEMM_PARAMS: Dict[str, Tuple[int, ...]] = {
    "bm": (8, 16, 32, 64, 128, 256, 512),
    "bn": (128, 256, 512, 1024),
    "bk": (32, 64, 128, 256, 512, 1024, 2048),
    "k_unroll": (1, 2, 4, 8),
    "k_split": (1, 2, 4, 8, 16, 32, 64),
    "order": (0, 1),
    "acc32": (0, 1),
    "prefetch": (1, 2, 3),
}

GEMM_INPUTS = ("M", "N", "K", "dtype_bits", "trans_a", "trans_b")


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _ceil_div(a, b) * b


def _tile_bytes(rows: int, cols: int, bpe: int) -> int:
    """VMEM bytes of a (rows, cols) array: rows pad to the dtype's sublane
    tile (8 for 32-bit, 16 for 16-bit), cols to the 128-lane tile."""
    return _round_up(rows, SUBLANE * max(4 // bpe, 1)) * _round_up(cols, LANE) \
        * bpe


def gemm_vmem_bytes(cfg: Mapping[str, int], dtype_bits: int) -> int:
    """Scoped VMEM of the Pallas GEMM for a configuration.

    Pallas double-buffers every input and output block whatever
    ``prefetch`` says; the kernel adds its accumulator scratch and the f32
    result of each block dot."""
    bpe = dtype_bits // 8
    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
    blocks = _tile_bytes(bm, bk, bpe) + _tile_bytes(bk, bn, bpe) \
        + _tile_bytes(bm, bn, bpe)
    acc = _tile_bytes(bm, bn, 4 if cfg["acc32"] else bpe)
    return 2 * blocks + acc + _tile_bytes(bm, bn, 4) + MOSAIC_SCRATCH_BYTES


def gemm_default_config(M: int, N: int, K: int, dtype_bits: int = 16
                        ) -> Config:
    """The GEMM's configuration where no tuned tier has one: blocks from
    the shape alone.

    ``bn`` and ``bk`` are the widest multiples of the lane tile, up to
    2560, that divide N and K rounded up to the lane tile: no weight pads
    beyond that (it is padded inside every call), and a 512-row block of
    that width fits ``VMEM_LIMIT_BYTES`` even in f32.  ``bm`` is the
    largest multiple of the sublane tile in [256, 512] that divides M
    rounded up to the sublane tile, so M pads no further; where none does,
    M is cut into the fewest blocks of at most 512 rows, all of one size,
    which pads it by less than a sublane tile a block."""
    widest = lambda d: max(b for b in range(LANE, 2561, LANE)
                           if _round_up(d, LANE) % b == 0)
    sub = SUBLANE * max(32 // dtype_bits, 1)
    bm = max((b for b in range(256, 513, sub) if _round_up(M, sub) % b == 0),
             default=_round_up(_ceil_div(M, _ceil_div(M, 512)), sub))
    return {"bm": bm, "bn": widest(N), "bk": widest(K), "k_unroll": 1,
            "k_split": 1, "order": 0, "acc32": 1, "prefetch": 2}


def gmm_default_tiles(M: int, N: int, K: int, dtype_bits: int = 16
                      ) -> Optional[Tuple[int, int, int]]:
    """(tm, tk, tn) of the Pallas grouped GEMM of ``M`` rows against
    (groups, K, N) weights, from the shape alone; None where XLA's ragged
    dot is the faster implementation."""
    sub = SUBLANE * max(32 // dtype_bits, 1)
    tm = min(128, _round_up(M, sub))
    return tm, K, N


def gemm_is_legal(cfg: Mapping[str, int], inputs: Mapping[str, int]) -> bool:
    """Membership test for X (paper §4: >99.9% of X-hat is illegal on GPU;
    our TPU space is less hostile but still majority-illegal for small inputs)."""
    M, N, K = inputs["M"], inputs["N"], inputs["K"]
    bits = inputs["dtype_bits"]
    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
    # -- resource limits ----------------------------------------------------
    if gemm_vmem_bytes(cfg, bits) > VMEM_LIMIT_BYTES:
        return False
    # -- alignment: lane/sublane tiles must be respected by the block shape --
    if bm % SUBLANE or bn % LANE:
        return False
    # bk must pack whole (sublane x lane) input tiles for both operands.
    if bk % LANE:
        return False
    # -- reduction splitting must have something to split --------------------
    k_steps = _ceil_div(K, bk)
    if cfg["k_split"] > k_steps:
        return False
    # unroll must not exceed the per-split sequential step count
    if cfg["k_unroll"] > max(1, _ceil_div(k_steps, cfg["k_split"])):
        return False
    # fp32 IO requires fp32 accumulation on the MXU
    if bits == 32 and not cfg["acc32"]:
        return False
    # -- gross-waste guards: a block larger than the (tile-padded) problem
    #    allocates VMEM and MXU passes for pure padding.  The paper's X
    #    likewise excludes configs that cannot execute safely/meaningfully. --
    if bm > _round_up(M, SUBLANE) or bn > _round_up(N, LANE) \
            or bk > _round_up(K, LANE):
        return False
    return True


GEMM_SPACE = ParamSpace(
    name="gemm",
    params=GEMM_PARAMS,
    input_params=GEMM_INPUTS,
    is_legal=gemm_is_legal,
)


# ---------------------------------------------------------------------------
# CONV: O[K,P,Q,N] = sum_c I[C,H,W,N] * F[C,R,S,K]   (paper §3.3)
#
# Implicit-GEMM view: (M', N', K') = (N*P*Q, K, C*R*S).  Tiling follows the
# shifted-window formulation (DESIGN.md §3): the kernel iterates over (r, s)
# filter offsets with statically shifted VMEM slices, so the tunables are the
# implicit-GEMM blocks plus the C-reduction split (paper's C_S, C_L, C_G).
# ---------------------------------------------------------------------------

CONV_PARAMS: Dict[str, Tuple[int, ...]] = {
    "b_npq": (8, 16, 32, 64, 128, 256, 512),
    "b_k": (128, 256, 512),
    "b_c": (32, 64, 128, 256, 512),
    "rs_unroll": (1, 2, 4),
    "c_split": (1, 2, 4, 8, 16),
    "order": (0, 1),
    "acc32": (0, 1),
    "prefetch": (1, 2, 3),
}

CONV_INPUTS = ("N", "H", "W", "C", "K", "R", "S", "dtype_bits")


def conv_out_shape(inputs: Mapping[str, int]) -> Tuple[int, int]:
    """'SAME'-padded unit-stride output spatial shape (DeepBench convention)."""
    return inputs["H"], inputs["W"]


CONV_W_TILE = 16        # output width padding: the bf16 sublane tile


def conv_blocks(cfg: Mapping[str, int], inputs: Mapping[str, int]
                ) -> Dict[str, int]:
    """The blocks ``ops.conv2d`` runs a config with: output rows per block
    ``b_p``, the tile-padded output width ``Q``, and the channel/filter
    blocks after fitting them to C and K (one channel block spanning the
    whole channel dim when ``b_c >= C``)."""
    P = inputs["H"]
    Q = _round_up(inputs["W"], CONV_W_TILE)
    C, K = inputs["C"], inputs["K"]
    b_k, b_c, cs = cfg["b_k"], cfg["b_c"], cfg["c_split"]
    while b_k > K and b_k > LANE:
        b_k //= 2
    if b_c >= C:
        b_c, cs = C, 1
    while cs > 1 and b_c * cs > C:
        cs //= 2
    b_p = max(min(cfg["b_npq"] // Q, P), 1)
    while P % b_p:
        b_p -= 1
    return {"b_p": b_p, "Q": Q, "b_c": b_c, "c_split": cs, "b_k": b_k}


def conv_vmem_bytes(cfg: Mapping[str, int], inputs: Mapping[str, int]) -> int:
    """Scoped VMEM of the Pallas conv: the double-buffered padded image
    slab, filter and output blocks, the accumulator scratch, and the
    window, dot result and running sum the kernel body holds."""
    bpe = inputs["dtype_bits"] // 8
    R, S = inputs["R"], inputs["S"]
    blk = conv_blocks(cfg, inputs)
    Q, b_c, b_k = blk["Q"], blk["b_c"], blk["b_k"]
    rows = blk["b_p"] * Q
    image = (inputs["H"] + R - 1) * _tile_bytes(Q + S - 1, b_c, bpe)
    filt = R * S * _tile_bytes(b_c, b_k, bpe)
    out = _tile_bytes(rows, b_k, bpe)
    acc = _tile_bytes(rows, b_k, 4 if cfg["acc32"] else bpe)
    body = _tile_bytes(rows, b_c, bpe) + 2 * _tile_bytes(rows, b_k, 4)
    return 2 * (image + filt + out) + acc + body + MOSAIC_SCRATCH_BYTES


def conv_is_legal(cfg: Mapping[str, int], inputs: Mapping[str, int]) -> bool:
    bits = inputs["dtype_bits"]
    P, Q = conv_out_shape(inputs)
    npq = inputs["N"] * P * Q
    C, K, R, S = inputs["C"], inputs["K"], inputs["R"], inputs["S"]
    if conv_vmem_bytes(cfg, inputs) > VMEM_LIMIT_BYTES:
        return False
    if cfg["b_npq"] % SUBLANE or cfg["b_k"] % LANE:
        return False
    # a channel block narrower than C is the slab's lane dim: whole lanes
    if cfg["b_c"] < C and cfg["b_c"] % LANE:
        return False
    c_steps = _ceil_div(C, cfg["b_c"])
    if cfg["c_split"] > c_steps:
        return False
    if cfg["rs_unroll"] > R * S:
        return False
    if bits == 32 and not cfg["acc32"]:
        return False
    if cfg["b_npq"] > _round_up(npq, SUBLANE) or cfg["b_k"] > _round_up(K, LANE) \
            or cfg["b_c"] > _round_up(C, LANE):
        return False
    return True


CONV_SPACE = ParamSpace(
    name="conv",
    params=CONV_PARAMS,
    input_params=CONV_INPUTS,
    is_legal=conv_is_legal,
)


# ---------------------------------------------------------------------------
# Beyond-paper tunable ops (paper §9 future work: "problems beyond GEMM and
# CONV").  Flash attention and the Mamba-2 SSD chunk scan expose block sizes
# through the same machinery.
# ---------------------------------------------------------------------------

ATTENTION_PARAMS: Dict[str, Tuple[int, ...]] = {
    "b_q": (128, 256, 512, 1024),
    "b_kv": (128, 256, 512, 1024, 2048),
    "acc32": (0, 1),
    "prefetch": (1, 2, 3),
}

ATTENTION_INPUTS = ("B", "Hq", "Hkv", "Lq", "Lkv", "D", "dtype_bits", "causal")


def attention_vmem_bytes(cfg: Mapping[str, int],
                         inputs: Mapping[str, int]) -> int:
    """Scoped VMEM of the Pallas flash attention: double-buffered q, k, v
    and output blocks, the running max/denominator/accumulator scratch, and
    the score-sized temporaries of the online softmax."""
    bpe = inputs["dtype_bits"] // 8
    d = inputs["D"]
    b_q = min(cfg["b_q"], max(inputs["Lq"], 1))
    b_kv = min(cfg["b_kv"], max(inputs["Lkv"], 1))
    blocks = 2 * _tile_bytes(b_q, d, bpe) + 2 * _tile_bytes(b_kv, d, bpe)
    scratch = 2 * _tile_bytes(b_q, 1, 4) + _tile_bytes(b_q, d, 4)
    scores = 4 * _tile_bytes(b_q, b_kv, 4)
    return 2 * blocks + scratch + scores + MOSAIC_SCRATCH_BYTES


def attention_is_legal(cfg: Mapping[str, int], inputs: Mapping[str, int]) -> bool:
    bits = inputs["dtype_bits"]
    if attention_vmem_bytes(cfg, inputs) > VMEM_LIMIT_BYTES:
        return False
    if bits == 32 and not cfg["acc32"]:
        return False
    if cfg["b_q"] > _round_up(inputs["Lq"], LANE) \
            or cfg["b_kv"] > _round_up(inputs["Lkv"], LANE):
        return False
    return True


ATTENTION_SPACE = ParamSpace(
    name="attention",
    params=ATTENTION_PARAMS,
    input_params=ATTENTION_INPUTS,
    is_legal=attention_is_legal,
)


SSD_PARAMS: Dict[str, Tuple[int, ...]] = {
    "chunk": (32, 64, 128, 256, 512),
    "b_heads": (1, 2, 4, 8),
    "acc32": (0, 1),
    "prefetch": (1, 2, 3),
}

SSD_INPUTS = ("B", "L", "H", "P", "S", "dtype_bits")   # P=head dim, S=state dim


def ssd_vmem_bytes(cfg: Mapping[str, int], inputs: Mapping[str, int]) -> int:
    """Scoped VMEM of the Pallas SSD scan: double-buffered x, dt, B, C and
    y blocks, the carried f32 state, and the (chunk, chunk) f32
    temporaries of the intra-chunk quadratic form (the unrolled head loop
    keeps some of them live per head)."""
    bpe = inputs["dtype_bits"] // 8
    p, s = inputs["P"], inputs["S"]
    c = min(cfg["chunk"], inputs["L"])
    bh = min(cfg["b_heads"], inputs["H"])
    blocks = bh * (2 * _tile_bytes(c, p, bpe) + _tile_bytes(c, 1, bpe)) \
        + 2 * _tile_bytes(c, s, bpe)
    state = bh * _tile_bytes(p, s, 4)
    body = (8 + 2 * bh) * _tile_bytes(c, c, 4) \
        + 4 * bh * _tile_bytes(c, max(p, s), 4)
    return 2 * blocks + state + body + MOSAIC_SCRATCH_BYTES


def ssd_is_legal(cfg: Mapping[str, int], inputs: Mapping[str, int]) -> bool:
    bits = inputs["dtype_bits"]
    if ssd_vmem_bytes(cfg, inputs) > VMEM_LIMIT_BYTES:
        return False
    if cfg["chunk"] > _round_up(inputs["L"], LANE):
        return False
    if bits == 32 and not cfg["acc32"]:
        return False
    return True


SSD_SPACE = ParamSpace(
    name="ssd",
    params=SSD_PARAMS,
    input_params=SSD_INPUTS,
    is_legal=ssd_is_legal,
)


SPACES: Dict[str, ParamSpace] = {
    "gemm": GEMM_SPACE,
    "conv": CONV_SPACE,
    "attention": ATTENTION_SPACE,
    "ssd": SSD_SPACE,
}


def gemm_input(M: int, N: int, K: int, dtype_bits: int = 16,
               trans_a: bool = False, trans_b: bool = False) -> Dict[str, int]:
    return {"M": int(M), "N": int(N), "K": int(K), "dtype_bits": int(dtype_bits),
            "trans_a": int(trans_a), "trans_b": int(trans_b)}


def conv_input(N: int, H: int, W: int, C: int, K: int, R: int, S: int,
               dtype_bits: int = 16) -> Dict[str, int]:
    return {"N": int(N), "H": int(H), "W": int(W), "C": int(C), "K": int(K),
            "R": int(R), "S": int(S), "dtype_bits": int(dtype_bits)}
