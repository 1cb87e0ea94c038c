"""replint Pallas auditor RP301–RP304: static VMEM, grid and naming checks on kernels.

| code  | invariant                                                          |
|-------|--------------------------------------------------------------------|
| RP301 | per-kernel VMEM footprint (in + out blocks + scratch) over budget  |
| RP302 | BlockSpec index-map arity ≠ grid rank (+ scalar-prefetch count), or index-map return rank ≠ block rank |
| RP303 | paged pool allocated without the reserved dump page (``n_pages`` where ``n_pages + 1`` is required) |
| RP304 | ``pallas_call`` without ``name=``: its custom call would be named after whatever jit or scope encloses it |

VMEM accounting: every ``pl.pallas_call`` site is parsed from the AST; each
``pl.BlockSpec`` block shape and ``pltpu.VMEM`` scratch shape is evaluated
symbolically against (a) module-level integer constants (``DEFAULT_BLOCK_D``
…), (b) a table of assumed dimension bindings for runtime sizes
(:data:`ASSUMED_DIMS` — worker count m, heads, head_dim, page size …), with
``bd``/``bw`` block names resolved to the module's own ``DEFAULT_BLOCK_D`` /
``DEFAULT_BLOCK_W`` when present. Footprint = Σ block numel × dtype bytes
(inputs assumed f32 — every kernel here upcasts to f32 in VMEM). Dims the
evaluator cannot resolve fall back to 128 and are flagged ``~`` in the table
so approximations are visible, never silent.

The same machinery renders the per-kernel VMEM table that lives between
``replint:vmem`` markers in ``src/repro/kernels/README.md`` (``--write-kernel-table``
regenerates it; ``--check-kernel-table`` fails on drift — the CI mode).
"""
from __future__ import annotations

import ast
import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from .engine import Finding, ModuleUnderLint, iter_python_files

DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024  # 16 MiB per-core VMEM

# Assumed bindings for runtime dimensions (representative serving/fleet
# sizes — deliberately on the large side so the budget check is conservative).
ASSUMED_DIMS: Dict[str, int] = {
    "m": 64,        # fleet worker count (paper regime m <= 64)
    "B": 8, "S": 8,  # decode batch / serve slots
    "KV": 8, "G": 4, "H": 32, "hd": 128,
    "W": 4096,       # dense cache window
    "P": 16,         # page size (tokens per page)
    "R": 864,        # ragged q rows per KV head: G·Tp, 6 × (8 + 1) slots·16
    "pps": 64,       # pages per slot
    "c": 64, "h": 8, "p": 64, "n": 64,   # SSD chunk/heads/head_dim/state
    "b": 4, "nc": 4,
    "dp": 8192,      # padded aggregation dim
    # wcwmed_leaf's blocks, bw workers x br rows x bc lanes in and bo x bc
    # out, as kernels/wcwmed.py _leaf_block sizes them at m = 64 in f32
    "bw": 64, "br": 16, "bo": 16, "bc": 896,
}
_FALLBACK_DIM = 128

DTYPE_BYTES = {
    "float32": 4, "f32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "bool": 1,
    "float64": 8, "int64": 8,
}

MARK_BEGIN = "<!-- replint:vmem:begin -->"
MARK_END = "<!-- replint:vmem:end -->"


def _dotted(node: ast.AST) -> Optional[str]:
    parts = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return None


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def _module_int_consts(tree: ast.Module) -> Dict[str, int]:
    out = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, int):
            out[node.targets[0].id] = node.value.value
    return out


@dataclasses.dataclass
class _Env:
    consts: Dict[str, int]
    assumed_used: set

    def lookup(self, name: str) -> Optional[int]:
        if name in self.consts:
            return self.consts[name]
        # block-size names resolve to the module's own default tile constants
        if name == "bd" and "DEFAULT_BLOCK_D" in self.consts:
            self.assumed_used.add(f"bd={self.consts['DEFAULT_BLOCK_D']}")
            return self.consts["DEFAULT_BLOCK_D"]
        if name in ("bw", "block_w") and "DEFAULT_BLOCK_W" in self.consts:
            self.assumed_used.add(f"{name}={self.consts['DEFAULT_BLOCK_W']}")
            return self.consts["DEFAULT_BLOCK_W"]
        if name in ASSUMED_DIMS:
            self.assumed_used.add(f"{name}={ASSUMED_DIMS[name]}")
            return ASSUMED_DIMS[name]
        return None


def _eval_dim(node: ast.AST, env: _Env) -> Tuple[int, bool]:
    """Evaluate one shape-dim expression -> (value, exact). ``exact`` is
    False once an assumed or fallback binding entered the computation."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value, True
    if isinstance(node, ast.Constant) and node.value is None:
        return 1, True               # a squeezed block dim: one element
    if isinstance(node, ast.Name):
        v = env.lookup(node.id)
        if v is not None:
            return v, node.id in env.consts
        env.assumed_used.add(f"{node.id}?={_FALLBACK_DIM}")
        return _FALLBACK_DIM, False
    if isinstance(node, ast.BinOp):
        l, le = _eval_dim(node.left, env)
        r, re_ = _eval_dim(node.right, env)
        ok = le and re_
        if isinstance(node.op, ast.Add):
            return l + r, ok
        if isinstance(node.op, ast.Sub):
            return l - r, ok
        if isinstance(node.op, ast.Mult):
            return l * r, ok
        if isinstance(node.op, ast.FloorDiv):
            return (l // r if r else _FALLBACK_DIM), ok
        if isinstance(node.op, ast.Mod):
            return (l % r if r else 0), ok
    if isinstance(node, ast.Call):
        name = _dotted(node.func)
        vals = [_eval_dim(a, env) for a in node.args]
        if vals and name in ("min", "max"):
            f = min if name == "min" else max
            return f(v for v, _ in vals), all(e for _, e in vals)
        if vals and name in ("pl.cdiv", "cdiv") and len(vals) == 2:
            (a, ae), (b, be) = vals
            return (-(-a // b) if b else _FALLBACK_DIM), ae and be
    env.assumed_used.add(f"<{type(node).__name__}>?={_FALLBACK_DIM}")
    return _FALLBACK_DIM, False


def _eval_shape(node: ast.AST, env: _Env) -> Optional[Tuple[int, ...]]:
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(_eval_dim(el, env)[0] for el in node.elts)
    return None


def _dtype_bytes(node: Optional[ast.AST]) -> int:
    if node is None:
        return 4
    name = (_dotted(node) or "").split(".")[-1]
    return DTYPE_BYTES.get(name, 4)


def _fn_arity(fn_node: ast.AST, mod: ModuleUnderLint
              ) -> Tuple[Optional[int], Optional[int]]:
    """(n_params, return_tuple_rank) of a BlockSpec index map (Lambda or a
    Name resolving to a def in this module)."""
    if isinstance(fn_node, ast.Lambda):
        rank = len(fn_node.body.elts) if isinstance(fn_node.body, ast.Tuple) \
            else None
        return len(fn_node.args.args), rank
    if isinstance(fn_node, ast.Name):
        for n in ast.walk(mod.tree):
            if isinstance(n, ast.FunctionDef) and n.name == fn_node.id:
                rank = None
                for r in ast.walk(n):
                    if isinstance(r, ast.Return) \
                            and isinstance(r.value, ast.Tuple):
                        rank = len(r.value.elts)
                return len(n.args.args), rank
    return None, None


@dataclasses.dataclass
class BlockInfo:
    label: str                       # in[0] / out[1] / scratch[2]
    shape: Optional[Tuple[int, ...]]
    nbytes: int


@dataclasses.dataclass
class KernelSite:
    """One ``pl.pallas_call`` site with its computed VMEM budget line."""
    path: str
    line: int
    func: str                        # enclosing function name
    grid_rank: Optional[int]
    grid_src: str
    blocks: List[BlockInfo]
    assumed: List[str]
    prefetch: int = 0

    @property
    def vmem_bytes(self) -> int:
        return sum(b.nbytes for b in self.blocks)


def _enclosing_func_name(mod: ModuleUnderLint, node: ast.AST) -> str:
    fn = mod.enclosing_function(node)
    return fn.name if fn is not None else "<module>"


def audit_module(mod: ModuleUnderLint,
                 budget: int = DEFAULT_VMEM_BUDGET
                 ) -> Tuple[List[KernelSite], List[Finding]]:
    """All pallas_call sites in one module, plus RP30x findings."""
    sites: List[KernelSite] = []
    findings: List[Finding] = []
    consts = _module_int_consts(mod.tree)

    for call in ast.walk(mod.tree):
        if not isinstance(call, ast.Call):
            continue
        if (_dotted(call.func) or "").split(".")[-1] != "pallas_call":
            continue
        env = _Env(dict(consts), set())
        prefetch = 0
        grid_node = _kw(call, "grid")
        in_specs = _kw(call, "in_specs")
        out_specs = _kw(call, "out_specs")
        scratch = _kw(call, "scratch_shapes")
        gs = _kw(call, "grid_spec")
        if gs is not None and isinstance(gs, ast.Name):
            # grid_spec bound to a local: chase the assignment in this function
            gs_name = gs.id
            owner = mod.enclosing_function(call)
            for n in ast.walk(owner if owner is not None else mod.tree):
                if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                        and isinstance(n.targets[0], ast.Name) \
                        and n.targets[0].id == gs_name:
                    gs = n.value
                    break
        if isinstance(gs, ast.Call):
            grid_node = _kw(gs, "grid") or grid_node
            in_specs = _kw(gs, "in_specs") or in_specs
            out_specs = _kw(gs, "out_specs") or out_specs
            scratch = _kw(gs, "scratch_shapes") or scratch
            pf = _kw(gs, "num_scalar_prefetch")
            if isinstance(pf, ast.Constant) and isinstance(pf.value, int):
                prefetch = pf.value

        grid_rank = len(grid_node.elts) \
            if isinstance(grid_node, (ast.Tuple, ast.List)) else None
        grid_src = ast.unparse(grid_node) if grid_node is not None else "?"

        out_shape = _kw(call, "out_shape")
        out_dtypes: List[Optional[ast.AST]] = []
        shapes = out_shape.elts if isinstance(out_shape, (ast.Tuple, ast.List)) \
            else ([out_shape] if out_shape is not None else [])
        for s in shapes:
            out_dtypes.append(s.args[1] if isinstance(s, ast.Call)
                              and len(s.args) > 1 else None)

        blocks: List[BlockInfo] = []

        def handle_spec(spec: ast.AST, label: str, dtype_node=None):
            if not isinstance(spec, ast.Call):
                return
            shape_node = spec.args[0] if spec.args else None
            fn_node = spec.args[1] if len(spec.args) > 1 else None
            shape = _eval_shape(shape_node, env) if shape_node is not None \
                else None
            nbytes = 0
            if shape:
                numel = 1
                for d in shape:
                    numel *= max(d, 1)
                nbytes = numel * _dtype_bytes(dtype_node)
            blocks.append(BlockInfo(label, shape, nbytes))
            if fn_node is not None and grid_rank is not None:
                nargs, ret_rank = _fn_arity(fn_node, mod)
                expected = grid_rank + prefetch
                if nargs is not None and nargs != expected:
                    findings.append(Finding(
                        "RP302", mod.path, spec.lineno,
                        f"index map of {label} takes {nargs} args but grid "
                        f"rank {grid_rank} + {prefetch} scalar-prefetch "
                        f"refs = {expected}"))
                block_rank = len(shape_node.elts) \
                    if isinstance(shape_node, (ast.Tuple, ast.List)) else None
                if ret_rank is not None and block_rank is not None \
                        and ret_rank != block_rank:
                    findings.append(Finding(
                        "RP302", mod.path, spec.lineno,
                        f"index map of {label} returns {ret_rank} indices "
                        f"for a rank-{block_rank} block"))

        if isinstance(in_specs, (ast.Tuple, ast.List)):
            for i, spec in enumerate(in_specs.elts):
                handle_spec(spec, f"in[{i}]")
        outs = out_specs.elts if isinstance(out_specs, (ast.Tuple, ast.List)) \
            else ([out_specs] if out_specs is not None else [])
        for i, spec in enumerate(outs):
            handle_spec(spec, f"out[{i}]",
                        out_dtypes[i] if i < len(out_dtypes) else None)
        if isinstance(scratch, (ast.Tuple, ast.List)):
            for i, sc in enumerate(scratch.elts):
                if not isinstance(sc, ast.Call):
                    continue
                kind = (_dotted(sc.func) or "").split(".")[-1]
                if kind != "VMEM":   # SMEM scalars are not VMEM-resident
                    continue
                shape = _eval_shape(sc.args[0], env) if sc.args else None
                dtype_node = sc.args[1] if len(sc.args) > 1 else None
                nbytes = 0
                if shape:
                    numel = 1
                    for d in shape:
                        numel *= max(d, 1)
                    nbytes = numel * _dtype_bytes(dtype_node)
                blocks.append(BlockInfo(f"scratch[{i}]", shape, nbytes))

        if _kw(call, "name") is None:
            findings.append(Finding(
                "RP304", mod.path, call.lineno,
                "pallas_call without name= — the custom call takes the name "
                "of whatever jit or named scope encloses it, and trace "
                "readers that match kernels by name lose it"))

        site = KernelSite(mod.path, call.lineno,
                          _enclosing_func_name(mod, call),
                          grid_rank, grid_src, blocks,
                          sorted(env.assumed_used), prefetch)
        sites.append(site)
        if site.vmem_bytes > budget:
            findings.append(Finding(
                "RP301", mod.path, call.lineno,
                f"kernel '{site.func}' VMEM footprint "
                f"{site.vmem_bytes / 2**20:.2f} MiB exceeds budget "
                f"{budget / 2**20:.0f} MiB"))

    findings.extend(_check_dump_page(mod))
    return sites, findings


def _check_dump_page(mod: ModuleUnderLint) -> List[Finding]:
    """RP303: in modules using the block-table idiom (``np.full(...,
    n_pages)`` as the unallocated sentinel), every page-pool allocation whose
    leading dim involves ``n_pages`` must reserve the dump page
    (``n_pages + 1``)."""
    has_table_sentinel = False
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) \
                and (_dotted(node.func) or "").endswith("full"):
            for arg in node.args[1:] + [k.value for k in node.keywords]:
                d = _dotted(arg)
                if d is not None and d.split(".")[-1] == "n_pages":
                    has_table_sentinel = True
    if not has_table_sentinel:
        return []

    findings = []
    _ALLOC = {"zeros", "empty", "ones", "full"}
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and (_dotted(node.func) or "").split(".")[-1] in _ALLOC
                and node.args):
            continue
        shape = node.args[0]
        if not isinstance(shape, (ast.Tuple, ast.List)) or not shape.elts:
            continue
        lead = shape.elts[0]
        uses_n_pages = any(isinstance(n, ast.Name) and n.id == "n_pages"
                           for n in ast.walk(lead))
        if not uses_n_pages:
            continue
        reserved = isinstance(lead, ast.BinOp) \
            and isinstance(lead.op, ast.Add) \
            and ((isinstance(lead.right, ast.Constant) and lead.right.value == 1)
                 or (isinstance(lead.left, ast.Constant) and lead.left.value == 1))
        if not reserved:
            findings.append(Finding(
                "RP303", mod.path, node.lineno,
                "page pool sized by 'n_pages' without the reserved dump page "
                "— allocate 'n_pages + 1' (block tables point unallocated "
                "logical pages at the last physical page)"))
    return findings


def audit_paths(paths: List[Path], budget: int = DEFAULT_VMEM_BUDGET
                ) -> Tuple[List[KernelSite], List[Finding]]:
    """Audit every file under ``paths`` that mentions ``pallas_call`` or the
    page-table idiom (so serve/cache.py gets the RP303 check too)."""
    sites: List[KernelSite] = []
    findings: List[Finding] = []
    for path in iter_python_files(paths):
        text = path.read_text()
        if "pallas_call" not in text and "n_pages" not in text:
            continue
        mod = ModuleUnderLint(path)
        s, f = audit_module(mod, budget)
        sites.extend(s)
        findings.extend(f)
    return sites, findings


# ---------------------------------------------------------------------------
# kernels/README.md VMEM table
# ---------------------------------------------------------------------------


def vmem_table(sites: List[KernelSite],
               budget: int = DEFAULT_VMEM_BUDGET) -> str:
    """Markdown table of per-kernel VMEM footprints (the generated block in
    kernels/README.md). ``~`` marks footprints that used assumed dims."""
    lines = [
        "| kernel | site | grid | VMEM (KiB) | budget | assumed dims |",
        "|---|---|---|---:|---|---|",
    ]
    for s in sorted(sites, key=lambda s: (s.path, s.line)):
        kib = s.vmem_bytes / 1024
        approx = "~" if s.assumed else ""
        status = "over budget" if s.vmem_bytes > budget else "ok"
        assumed = ", ".join(s.assumed) if s.assumed else "—"
        fname = s.path.rsplit("/", 1)[-1]
        lines.append(
            f"| `{s.func}` | `{fname}:{s.line}` | `{s.grid_src}` "
            f"| {approx}{kib:.1f} | {status} | {assumed} |")
    lines.append("")
    lines.append(f"Budget: {budget / 2**20:.0f} MiB/core. Generated by "
                 f"`python tools/lint.py --write-kernel-table`; CI checks "
                 f"drift with `--check-kernel-table`. Assumed runtime dims "
                 f"come from `tools/lint/pallas_audit.py:ASSUMED_DIMS`.")
    return "\n".join(lines)


def render_readme(readme_text: str, table: str) -> str:
    block = f"{MARK_BEGIN}\n{table}\n{MARK_END}"
    if MARK_BEGIN in readme_text and MARK_END in readme_text:
        head, rest = readme_text.split(MARK_BEGIN, 1)
        _, tail = rest.split(MARK_END, 1)
        return head + block + tail
    sep = "" if readme_text.endswith("\n\n") else \
        ("\n" if readme_text.endswith("\n") else "\n\n")
    return (readme_text + sep + "## Static VMEM audit (generated)\n\n"
            + block + "\n")
