"""Pallas tiling checker (`tiling`).

Mosaic rejects (or silently pads) block shapes that break its layout
rules; the kernels in `ops/` encode the discipline in their tile
pickers (`_pick_tile_n`, `_pick_tile_w`): a row tile must DIVIDE the
array extent (the grid is `n // tile`) and be a MULTIPLE OF 8 (the f32
sublane quantum), under a VMEM budget. This checker keeps new kernel
code on that discipline:

- `block-literal` — an integer literal used as the leading (row)
  dimension of a `pl.BlockSpec((r, ...))` that is not a multiple of 8.
  In a 2-D block that includes 1: the row dimension is then the block's
  second-to-last, and a `(1, C)` block over `[n_tiles, C]` is what
  Pallas's TPU lowering refused in bn_relu's partial sums (legal only
  where the array's own dimension is 1: say so with the escape hatch).
  Per-row outputs ride as `[n, 1, C]` with `(1, 1, C)` blocks, where the
  leading 1 is a plain grid dimension.
- `unvalidated-tile` — a `pallas_call(grid=(n // t, ...))` whose tile
  `t` was NOT produced by a `_pick_tile_*` helper in the same function
  and has no `n % t` divisibility guard: when `t` does not divide `n`
  the grid silently drops the remainder rows.

Plus the *executed* half (`deep_check`, run under `lint_cli check
--deep` and the acceptance test): imports the real pickers and
property-checks the invariants their docstrings promise over a sweep of
(n, c) extents — divides-n, multiple-of-8-or-full, within-bound. That
is the "where cheap, beyond the ast" layer: the checker validates the
functions the static rules trust.

Scope: `ops/` files. Escape hatch: `# lint: tiling-ok(reason)`.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from bigdl_tpu.analysis.core import Checker, Finding, SourceFile
from bigdl_tpu.analysis.donation import call_name

_DEFAULT_DIRS = ("ops/",)


class TilingChecker(Checker):
    """Checks `ops/` Pallas block shapes against the Mosaic
    multiple-of-8/divisor discipline the `_pick_tile_*` helpers encode;
    `--deep` property-checks the real pickers. Details: module docstring."""

    id = "tiling"

    def __init__(self, all_files: bool = False,
                 dirs: Tuple[str, ...] = _DEFAULT_DIRS):
        self.all_files = all_files
        self.dirs = dirs

    def _applies(self, src: SourceFile) -> bool:
        return self.all_files or any(d in src.rel for d in self.dirs)

    def check(self, src: SourceFile) -> List[Finding]:
        if not self._applies(src):
            return []
        raw: List[Tuple[str, int, str, str]] = []
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_function(node, raw)
        return self.make_findings(src, raw)

    # ----------------------------------------------------------- static
    def _check_function(self, fn, raw: List[Tuple[str, int, str, str]]):
        picked: Set[str] = set()   # names assigned from _pick_tile_*
        guarded: Set[str] = set()  # names appearing in an `n % t` check
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call):
                cn = call_name(node.value.func) or ""
                if cn.startswith("_pick_tile") or cn.startswith("pick_tile"):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            picked.add(t.id)
            if isinstance(node, ast.BinOp) and \
                    isinstance(node.op, ast.Mod):
                if isinstance(node.right, ast.Name):
                    guarded.add(node.right.id)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            cn = call_name(node.func)
            if cn == "BlockSpec":
                self._check_blockspec(node, raw)
            elif cn == "pallas_call":
                self._check_grid(node, picked, guarded, raw)

    @staticmethod
    def _check_blockspec(node: ast.Call,
                         raw: List[Tuple[str, int, str, str]]):
        shape = node.args[0] if node.args else None
        for kw in node.keywords:
            if kw.arg == "block_shape":
                shape = kw.value
        if not isinstance(shape, (ast.Tuple, ast.List)) or \
                len(shape.elts) < 2:
            return  # 1-D blocks ([C] broadcast rows) have no row dim
        lead = shape.elts[0]
        if isinstance(lead, ast.Constant) and \
                isinstance(lead.value, int) and \
                not isinstance(lead.value, bool):
            r = lead.value
            if r % 8 != 0 and (r > 1 or len(shape.elts) == 2):
                raw.append((
                    "block-literal", lead.lineno,
                    f"BlockSpec row dimension {r} is not a multiple of 8 "
                    f"(the f32 sublane quantum Mosaic tiles by)",
                    "use a multiple of 8, size it with _pick_tile_n, or "
                    "carry per-row outputs as [n, 1, C] with (1, 1, C) "
                    "blocks"))

    @staticmethod
    def _check_grid(node: ast.Call, picked: Set[str], guarded: Set[str],
                    raw: List[Tuple[str, int, str, str]]):
        grid = None
        for kw in node.keywords:
            if kw.arg == "grid":
                grid = kw.value
        if grid is None:
            return
        dims = grid.elts if isinstance(grid, (ast.Tuple, ast.List)) \
            else [grid]
        for dim in dims:
            if not (isinstance(dim, ast.BinOp) and
                    isinstance(dim.op, ast.FloorDiv) and
                    isinstance(dim.right, ast.Name)):
                continue
            t = dim.right.id
            if t in picked or t in guarded:
                continue
            raw.append((
                "unvalidated-tile", dim.lineno,
                f"grid `... // {t}` uses a tile that is neither produced "
                f"by a _pick_tile_* helper nor divisibility-checked — a "
                f"non-dividing tile silently drops remainder rows",
                f"size `{t}` with _pick_tile_n/_pick_tile_w (divisor + "
                f"multiple-of-8 discipline) or assert n % {t} == 0"))


# ---------------------------------------------------------------------- #
# executed invariants (the --deep layer)
# ---------------------------------------------------------------------- #

def deep_check() -> List[Finding]:
    """Import the real tile pickers and property-check their promised
    invariants over a sweep of extents. Returns findings (empty = the
    pickers hold); import failures become findings, not crashes — the
    deep layer must degrade loudly, never silently."""
    findings: List[Finding] = []

    def bad(path, rule, msg, hint):
        findings.append(Finding("tiling", rule, path, 1, msg, hint,
                                key=f"tiling:{rule}:{msg}"))

    try:
        from bigdl_tpu.ops.bn_relu_kernel import _pick_tile_n
    except Exception as e:  # pragma: no cover - import env problem
        bad("bigdl_tpu/ops/bn_relu_kernel.py", "deep-import",
            f"cannot import _pick_tile_n: {e!r}", "fix the import")
    else:
        for n in (1, 7, 8, 16, 24, 40, 56, 96, 120, 128, 1000, 4096,
                  12288):
            for c in (1, 3, 8, 64, 129, 512):
                t = _pick_tile_n(n, c)
                if n % t != 0:
                    bad("bigdl_tpu/ops/bn_relu_kernel.py",
                        "deep-invariant",
                        f"_pick_tile_n({n}, {c}) = {t} does not divide n",
                        "the grid would drop remainder rows")
                elif t != n and t % 8 != 0:
                    bad("bigdl_tpu/ops/bn_relu_kernel.py",
                        "deep-invariant",
                        f"_pick_tile_n({n}, {c}) = {t} is neither n nor "
                        f"a multiple of 8",
                        "Mosaic sublane quantum violated")
    try:
        from bigdl_tpu.ops.stem_kernel import _pick_tile_w
    except Exception as e:  # pragma: no cover
        bad("bigdl_tpu/ops/stem_kernel.py", "deep-import",
            f"cannot import _pick_tile_w: {e!r}", "fix the import")
    else:
        import inspect
        sig = inspect.signature(_pick_tile_w)
        for w in (1, 7, 8, 14, 16, 28, 56, 112, 224, 512):
            try:
                t = _pick_tile_w(w) if len(sig.parameters) == 1 \
                    else _pick_tile_w(w, 64)
            except Exception as e:
                bad("bigdl_tpu/ops/stem_kernel.py", "deep-invariant",
                    f"_pick_tile_w({w}) raised {e!r}",
                    "the picker must accept any positive extent")
                continue
            if w % t != 0:
                bad("bigdl_tpu/ops/stem_kernel.py", "deep-invariant",
                    f"_pick_tile_w({w}) = {t} does not divide w",
                    "the grid would drop remainder columns")
    return findings
