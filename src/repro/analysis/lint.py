"""AST repo lint: the architecture rules PRs 3-5 established, enforced.

Each rule is a small AST visitor registered in :data:`RULES`; the CLI
(``python -m repro.analysis``) runs them over ``src/`` and exits nonzero on
any finding.  Rules are path-scoped with repo-relative posix paths, so test
fixtures can exercise them with virtual paths (``lint_source(snippet,
"src/repro/models/fake.py")``).

Rule catalog (docs/analysis.md mirrors this):

  no-pallas-outside-kernels   ``pl.pallas_call`` belongs in ``kernels/``;
                              everything else goes through ``kernels.ops``
                              or the backend registry.
  no-direct-kernel-imports    the kernel implementation modules
                              (``bitplane_gemv``/``bitplane_gemm``/``majx``)
                              are private to the ``kernels`` package — call
                              sites import ``kernels.ops`` / ``backends``.
  no-raw-pack-dicts           packs are ``PackedTensor`` pytrees; raw
                              ``{"planes": ..., "scale": ...}`` dicts may
                              only be built inside ``pud/packed.py`` (the
                              one legacy-coercion point).
  no-assert-in-kernels        ``assert`` inside kernel code is stripped
                              under ``python -O`` and invisible in a traced
                              kernel body — raise ``ContractViolation``.
  no-constant-prng-key        ``jax.random.key(0)``-style literal seeds in
                              library code produce hidden cross-call
                              correlation; thread keys (or derive them from
                              config seeds) instead.
  no-removed-jax-api          mesh-context APIs (``jax.set_mesh``) are
                              entered only through
                              ``launch/mesh.use_mesh``.
  no-recal-on-decode-path     ladder identification (Algorithm 1) is
                              minutes of work and must never run inside
                              the decode loop — the decode path
                              (``runtime/engine.py``, ``models/``) may not
                              import or call fleet recalibration; drift
                              recovery recalibrates from the controller
                              between steps (``runtime/drift.py``) and
                              hands the engine a finished pack.
  no-mesh-outside-launch-mesh device meshes (``jax.make_mesh`` /
                              ``jax.sharding.Mesh(...)``) are constructed
                              only by the ``launch/mesh.py`` factories, so
                              device-topology decisions live in one place;
                              call sites take a mesh as an argument.
  no-prefill-on-decode-wave   chunk-scheduling helpers (decode-path
                              functions with ``chunk`` in their name) may
                              not call whole-request prefill — a whole
                              prefill inside the decode wave stalls every
                              decoding slot for the full prompt length,
                              which is exactly what chunked prefill exists
                              to prevent; chunk helpers advance via
                              ``prefill_chunk`` only.
"""
from __future__ import annotations

import ast
import dataclasses
import os

#: Kernel implementation modules private to the kernels package.
KERNEL_MODULES = frozenset({"bitplane_gemv", "bitplane_gemm", "majx"})

#: jax attributes reached only through launch/mesh.py (rule:
#: no-removed-jax-api).
REMOVED_JAX_APIS = frozenset({"set_mesh"})

#: Fleet recalibration entrypoints (rule: no-recal-on-decode-path).
#: Anything that runs Algorithm-1 ladder identification — step-granular
#: serving must reach these only from the drift controller, never from
#: the decode loop itself.
RECALIBRATION_ENTRYPOINTS = frozenset({
    "calibrate_fleet", "identify_calibration", "identify_calibration_fn",
    "load_or_calibrate", "recalibrate_subarrays"})

#: Modules on the step-granular decode path (rule: no-recal-on-decode-path).
DECODE_PATH_PREFIXES = ("repro/runtime/engine.py", "repro/models/")

#: Whole-request prefill entrypoints (rule: no-prefill-on-decode-wave).
#: Chunk-scheduling helpers advance admitted prompts one chunk at a time;
#: reaching any of these from a chunk helper re-introduces the full-prompt
#: stall the chunked scheduler exists to remove.
WHOLE_PREFILL_ENTRYPOINTS = frozenset({
    "prefill", "_prefill", "_prefill_fn",
    "_prefill_bucketed", "_prefill_bucketed_fn"})


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


RULES: dict[str, "LintRule"] = {}


@dataclasses.dataclass(frozen=True)
class LintRule:
    id: str
    description: str
    check: object  # callable(tree, path) -> iterable[Finding]


def rule(rule_id: str, description: str):
    def register(fn):
        RULES[rule_id] = LintRule(rule_id, description, fn)
        return fn

    return register


def _norm(path) -> str:
    return str(path).replace(os.sep, "/")


def _in_kernels(path: str) -> bool:
    return "repro/kernels/" in _norm(path)


def _attr_chain(node: ast.AST) -> str:
    """Dotted name of an attribute/name chain ('jax.random.key'), '' if the
    chain bottoms out in something dynamic."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


@rule("no-pallas-outside-kernels",
      "pl.pallas_call is only lowered inside src/repro/kernels/")
def _check_pallas(tree: ast.AST, path: str):
    if _in_kernels(path):
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if chain.split(".")[-1] == "pallas_call":
            yield Finding(
                "no-pallas-outside-kernels", path, node.lineno,
                "pallas_call outside kernels/ — add a kernel module and "
                "expose it through kernels.ops / the backend registry")


def _imported_kernel_module(node: ast.AST) -> str | None:
    """The private kernel module an import statement reaches into, if any."""
    if isinstance(node, ast.Import):
        for alias in node.names:
            parts = alias.name.split(".")
            if "kernels" in parts and parts[-1] in KERNEL_MODULES:
                return alias.name
    if isinstance(node, ast.ImportFrom):
        mod = node.module or ""
        parts = mod.split(".") if mod else []
        if parts and parts[-1] in KERNEL_MODULES and (
                "kernels" in parts or node.level > 0):
            return mod
        if parts and parts[-1] == "kernels":
            for alias in node.names:
                if alias.name in KERNEL_MODULES:
                    return f"{mod}.{alias.name}"
    return None


@rule("no-direct-kernel-imports",
      "kernel implementation modules are private to the kernels package")
def _check_kernel_imports(tree: ast.AST, path: str):
    if _in_kernels(path):
        return
    for node in ast.walk(tree):
        mod = _imported_kernel_module(node)
        if mod is not None:
            yield Finding(
                "no-direct-kernel-imports", path, node.lineno,
                f"import of private kernel module {mod!r} — go through "
                "kernels.ops or kernels.backends")


def _is_raw_pack_dict(node: ast.AST) -> bool:
    if isinstance(node, ast.Dict):
        keys = {k.value for k in node.keys
                if isinstance(k, ast.Constant) and isinstance(k.value, str)}
        return {"planes", "scale"} <= keys
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id == "dict"):
        kws = {kw.arg for kw in node.keywords}
        return {"planes", "scale"} <= kws
    return False


@rule("no-raw-pack-dicts",
      "packs are typed PackedTensor pytrees; raw dicts only in pud/packed.py")
def _check_raw_packs(tree: ast.AST, path: str):
    if _norm(path).endswith("repro/pud/packed.py"):
        return
    for node in ast.walk(tree):
        if _is_raw_pack_dict(node):
            yield Finding(
                "no-raw-pack-dicts", path, node.lineno,
                "raw {'planes', 'scale'} pack construction — build a "
                "PackedTensor (pud/packed.py) instead")


@rule("no-assert-in-kernels",
      "assert in kernel code is stripped under -O and invisible in a trace")
def _check_kernel_asserts(tree: ast.AST, path: str):
    if not _in_kernels(path):
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.Assert):
            yield Finding(
                "no-assert-in-kernels", path, node.lineno,
                "bare assert in kernel code — raise ContractViolation "
                "(repro.analysis.errors) so the failure names the kernel "
                "and invariant")


@rule("no-constant-prng-key",
      "literal PRNG seeds in library code hide cross-call correlation")
def _check_prng(tree: ast.AST, path: str):
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        chain = _attr_chain(node.func)
        parts = chain.split(".")
        if parts[-1] not in ("PRNGKey", "key") or "random" not in parts:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, int):
            yield Finding(
                "no-constant-prng-key", path, node.lineno,
                f"{chain}({arg.value}) with a literal seed — thread an "
                "explicit key (fold_in per call site) or derive the seed "
                "from config")


@rule("no-removed-jax-api",
      "mesh-context APIs outside launch/mesh (use launch/mesh.use_mesh)")
def _check_removed_apis(tree: ast.AST, path: str):
    if _norm(path).endswith("repro/launch/mesh.py"):
        return  # the one module that installs mesh contexts
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and node.attr in REMOVED_JAX_APIS
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax"):
            yield Finding(
                "no-removed-jax-api", path, node.lineno,
                f"jax.{node.attr} is entered through "
                "repro.launch.mesh.use_mesh")


def _on_decode_path(path: str) -> bool:
    p = _norm(path)
    return any(f"src/{pre}" in p or p.startswith(pre) or f"/{pre}" in p
               for pre in DECODE_PATH_PREFIXES)


@rule("no-recal-on-decode-path",
      "the decode path must not import or call fleet recalibration")
def _check_decode_recal(tree: ast.AST, path: str):
    if not _on_decode_path(path):
        return
    msg = ("Algorithm-1 recalibration reached from the decode path — "
           "drift recovery runs it in the controller between steps "
           "(runtime/drift.py) and hands the engine a finished pack via "
           "stage_params")
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = {a.name.split(".")[-1] for a in node.names}
            hit = names & RECALIBRATION_ENTRYPOINTS
            if hit:
                yield Finding(
                    "no-recal-on-decode-path", path, node.lineno,
                    f"import of {sorted(hit)[0]!r}: {msg}")
        elif isinstance(node, ast.Call):
            tail = _attr_chain(node.func).split(".")[-1]
            if tail in RECALIBRATION_ENTRYPOINTS:
                yield Finding(
                    "no-recal-on-decode-path", path, node.lineno,
                    f"call to {tail!r}: {msg}")


@rule("no-prefill-on-decode-wave",
      "chunk scheduling helpers may not call whole-request prefill")
def _check_chunk_prefill(tree: ast.AST, path: str):
    if not _on_decode_path(path):
        return
    msg = ("whole-request prefill reached from a chunk-scheduling helper — "
           "a full-prompt prefill inside the decode wave stalls every "
           "decoding slot for the whole prompt; advance the slot with "
           "prefill_chunk and let admission handle un-chunked requests")
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if "chunk" not in fn.name:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            tail = _attr_chain(node.func).split(".")[-1]
            if tail in WHOLE_PREFILL_ENTRYPOINTS:
                yield Finding(
                    "no-prefill-on-decode-wave", path, node.lineno,
                    f"call to {tail!r} inside {fn.name!r}: {msg}")


@rule("no-mesh-outside-launch-mesh",
      "device meshes are constructed only by the launch/mesh.py factories")
def _check_mesh_construction(tree: ast.AST, path: str):
    if _norm(path).endswith("repro/launch/mesh.py"):
        return  # the one mesh factory module
    # Aliases `from jax.sharding import Mesh [as M]` binds in this module —
    # importing Mesh for annotations is fine, *calling* it is not.
    mesh_ctors = {a.asname or a.name
                  for node in ast.walk(tree)
                  if isinstance(node, ast.ImportFrom)
                  and node.module == "jax.sharding"
                  for a in node.names if a.name == "Mesh"}
    msg = ("mesh construction outside launch/mesh.py — use "
           "make_production_mesh / make_host_mesh / make_mesh_for_devices / "
           "parse_mesh_spec so device-topology decisions live in one place")
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        parts = chain.split(".")
        if (chain == "jax.sharding.Mesh"
                or (parts[0] == "jax" and parts[-1] == "make_mesh")):
            yield Finding("no-mesh-outside-launch-mesh", path, node.lineno,
                          f"{chain}(...): {msg}")
        elif isinstance(node.func, ast.Name) and node.func.id in mesh_ctors:
            yield Finding("no-mesh-outside-launch-mesh", path, node.lineno,
                          f"{node.func.id}(...): {msg}")


def lint_source(source: str, path: str) -> list[Finding]:
    """Lint one file's source text under a (possibly virtual) path."""
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as e:
        return [Finding("syntax-error", _norm(path), e.lineno or 0, str(e))]
    findings: list[Finding] = []
    for r in RULES.values():
        findings.extend(r.check(tree, _norm(path)))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def lint_file(path) -> list[Finding]:
    with open(path, encoding="utf-8") as f:
        return lint_source(f.read(), path)


def lint_paths(paths) -> list[Finding]:
    """Lint every ``.py`` file under the given files/directories."""
    findings: list[Finding] = []
    for root in paths:
        root = str(root)
        if os.path.isfile(root):
            findings.extend(lint_file(root))
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames
                           if not d.startswith(".") and d != "__pycache__"]
            for name in sorted(filenames):
                if name.endswith(".py"):
                    findings.extend(lint_file(os.path.join(dirpath, name)))
    return findings
