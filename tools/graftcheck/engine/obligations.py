"""GC010 parity obligations: the kernel <-> oracle map, machine-readable.

kernels.py's docstring map (GC006 checks membership) is parsed into one
OBLIGATION per public kernel: the kernel's signature, its oracle — a
repo-resolvable dotted symbol (``quorum.MajorityConfig.committed_index``),
a parity-suite file, and/or a reference citation (``majority.rs:70-124``)
— and the test files whose code exercises the kernel identifier.  The
whole set is emitted as ``parity_obligations.json`` (``--emit-obligations``)
and diffed against the committed baseline
``tools/graftcheck/parity_obligations.json`` both here (a stale baseline
is a GC010 violation) and as a CI artifact step, so an obligation can
never be dropped silently.  ``tests/test_sim_parity.py`` and
``tests/test_health_parity.py`` load the same JSON and assert they
exercise every obligation assigned to them.

Violations:
  * a kernel's map entry names a dotted repo symbol that no longer
    resolves (oracle rot — the GC005 analog for symbols);
  * a kernel's entry has NO machine-checkable oracle at all (no
    resolvable symbol, no parity-suite file, no reference citation);
  * the entry's parity-suite file does not exist;
  * the committed baseline disagrees with the extracted obligations.
"""

from __future__ import annotations

import ast
import json
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from ..core import Context, SourceFile, Violation

GC010 = "GC010"
GC010_SLUG = "parity-obligations"

BASELINE_RELPATH = "tools/graftcheck/parity_obligations.json"
DEFAULT_SUITE = "tests/test_sim_parity.py"

_CITE_RE = re.compile(r"\b([\w./-]+\.(?:rs|cpp|cc|h|go)):(\d+(?:-\d+)?)")
_PY_PATH_RE = re.compile(r"\b((?:tests|raft_tpu|tools)/[\w/]+\.py)\b")
_DOTTED_RE = re.compile(r"\b([A-Za-z_]\w*(?:\.[A-Za-z_]\w*)+)\b")


def _v(sf: SourceFile, lineno: int, message: str) -> Violation:
    return Violation(sf.display_path, lineno, GC010, GC010_SLUG, message)


# --- docstring map parsing --------------------------------------------------


class MapEntry:
    def __init__(self) -> None:
        self.names: List[str] = []
        self.text: List[str] = []

    def joined(self) -> str:
        return " ".join(t for t in self.text if t)


def parse_map(doc: str, public_names: Set[str]) -> List[MapEntry]:
    entries: List[MapEntry] = []
    current: Optional[MapEntry] = None
    for line in doc.splitlines():
        stripped = line.strip()
        if "<->" in line:
            left, _, right = line.partition("<->")
            current = MapEntry()
            current.names = [
                t for t in re.findall(r"\w+", left) if t in public_names
            ]
            current.text = [right.strip()]
            entries.append(current)
            continue
        if current is None:
            continue
        if not stripped:
            current = None  # blank line ends the map block
            continue
        indent = len(line) - len(line.lstrip())
        first = re.match(r"[A-Za-z_]\w*", stripped)
        if first and first.group(0) in public_names and indent <= 4:
            # name-continuation row ("zero_counters / \n count_events ...")
            current.names.append(first.group(0))
            rest = stripped[len(first.group(0)):].strip()
            if rest:
                current.text.append(rest)
        else:
            current.text.append(stripped)
    return entries


# --- repo symbol resolution -------------------------------------------------


class _Resolver:
    """Resolve dotted names / class names against the repo tree (AST only,
    nothing imported), following one level of ``from .x import Y``
    re-exports."""

    def __init__(self, repo_root: Path):
        self.repo_root = repo_root
        self._trees: Dict[Path, Optional[ast.Module]] = {}

    def _tree(self, path: Path) -> Optional[ast.Module]:
        if path not in self._trees:
            tree: Optional[ast.Module] = None
            if path.is_file():
                try:
                    tree = ast.parse(path.read_text(encoding="utf-8"))
                except SyntaxError:
                    tree = None
            self._trees[path] = tree
        return self._trees[path]

    def _module_file(self, pkg_dir: Path, name: str) -> Optional[Path]:
        for cand in (pkg_dir / f"{name}.py", pkg_dir / name / "__init__.py"):
            if cand.is_file():
                return cand
        return None

    def resolve_dotted(
        self, dotted: str
    ) -> Optional[Tuple[str, int, List[str]]]:
        """-> (repo-relative "file::qualname", lineno, params) or None."""
        parts = dotted.split(".")
        if parts[0] == "raft_tpu":
            parts = parts[1:]
        if not parts:
            return None
        pkg = self.repo_root / "raft_tpu"
        if parts[0][0].isupper():
            # Class-first form (Raft.tick_election): find the class.
            return self._resolve_class_first(parts)
        mod_file = self._module_file(pkg, parts[0])
        if mod_file is None:
            # Device-package modules (simref.host_pack_bits_g, chaos.
            # host_loss_draw) live one level down in raft_tpu/multiraft.
            mod_file = self._module_file(pkg / "multiraft", parts[0])
        if mod_file is None:
            return None
        if len(parts) == 1:
            return (self._rel(mod_file), 1, [])
        return self._resolve_in_module(mod_file, parts[1:])

    def _resolve_class_first(
        self, parts: List[str]
    ) -> Optional[Tuple[str, int, List[str]]]:
        cls = parts[0]
        needle = f"class {cls}"
        for path in sorted((self.repo_root / "raft_tpu").rglob("*.py")):
            try:
                if needle not in path.read_text(encoding="utf-8"):
                    continue
            except OSError:
                continue
            hit = self._resolve_in_module(path, parts)
            if hit is not None:
                return hit
        return None

    def _resolve_in_module(
        self,
        mod_file: Path,
        parts: Sequence[str],
        _visited: Optional[Set[Path]] = None,
    ) -> Optional[Tuple[str, int, List[str]]]:
        # _visited guards the re-export hop: a cyclic `from .a import X` /
        # `from .b import X` pair (mid-refactor state) must resolve to
        # None (oracle rot), not recurse forever.
        visited = _visited if _visited is not None else set()
        if mod_file in visited:
            return None
        visited.add(mod_file)
        tree = self._tree(mod_file)
        if tree is None:
            return None
        body: Sequence[ast.stmt] = tree.body
        qual: List[str] = []
        node: Optional[ast.AST] = None
        for i, part in enumerate(parts):
            found: Optional[ast.AST] = None
            for child in body:
                if (
                    isinstance(
                        child, (ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef)
                    )
                    and child.name == part
                ):
                    found = child
                    break
            if found is None and i == 0:
                # one level of re-export: from .x import part
                for child in body:
                    if isinstance(child, ast.ImportFrom) and any(
                        a.name == part or a.asname == part
                        for a in child.names
                    ):
                        if child.module is None:
                            continue
                        target = self._module_file(
                            mod_file.parent, child.module.split(".")[-1]
                        )
                        if target is not None:
                            return self._resolve_in_module(
                                target, parts, visited
                            )
            if found is None:
                return None
            qual.append(part)
            node = found
            body = found.body if isinstance(found, ast.ClassDef) else []
        params: List[str] = []
        lineno = getattr(node, "lineno", 1)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            params = [a.arg for a in node.args.args]
        return (f"{self._rel(mod_file)}::{'.'.join(qual)}", lineno, params)

    def resolve_in(
        self, relpath: str, parts: Sequence[str]
    ) -> Optional[Tuple[str, int, List[str]]]:
        """Resolve a qualname inside one named module (the simref-oracle
        path for bare class names like ``HealthOracle``)."""
        mod_file = self.repo_root / relpath
        if not mod_file.is_file():
            return None
        return self._resolve_in_module(mod_file, parts)

    def _rel(self, path: Path) -> str:
        try:
            return path.relative_to(self.repo_root).as_posix()
        except ValueError:
            return path.as_posix()


# --- extraction -------------------------------------------------------------


def _test_files_exercising(
    tests_root: Optional[Path], names: Set[str]
) -> Dict[str, List[str]]:
    """kernel name -> sorted repo-relative test files whose CODE uses it."""
    out: Dict[str, Set[str]] = {n: set() for n in names}
    if tests_root is None or not tests_root.is_dir():
        return {n: [] for n in names}
    for path in sorted(tests_root.rglob("*.py")):
        try:
            tree = ast.parse(path.read_text(encoding="utf-8"))
        except (SyntaxError, OSError):
            continue
        idents: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Name):
                idents.add(node.id)
            elif isinstance(node, ast.Attribute):
                idents.add(node.attr)
        rel = f"{tests_root.name}/{path.relative_to(tests_root).as_posix()}"
        for n in names & idents:
            out[n].add(rel)
    return {n: sorted(files) for n, files in out.items()}


def extract(
    sf: SourceFile, ctx: Context
) -> Tuple[Dict[str, object], List[Violation]]:
    """Extract the obligations document from kernels.py; returns
    (document, violations)."""
    violations: List[Violation] = []
    tree = sf.ast_tree
    public = {
        node.name: node
        for node in ast.iter_child_nodes(tree)
        if isinstance(node, ast.FunctionDef) and not node.name.startswith("_")
    }
    doc = ast.get_docstring(tree) or ""
    entries = parse_map(doc, set(public))
    by_name: Dict[str, MapEntry] = {}
    for entry in entries:
        for name in entry.names:
            by_name[name] = entry
    resolver = _Resolver(ctx.repo_root)
    tests = _test_files_exercising(ctx.tests_root, set(public))

    obligations: List[Dict[str, object]] = []
    for name in sorted(public):
        func = public[name]
        entry = by_name.get(name)
        oracle_text = entry.joined() if entry is not None else ""
        oracle_text = re.sub(r"\s+", " ", oracle_text).strip()
        cite_m = _CITE_RE.search(oracle_text)
        cite = f"{cite_m.group(1)}:{cite_m.group(2)}" if cite_m else None
        suite = DEFAULT_SUITE
        py_paths = _PY_PATH_RE.findall(oracle_text)
        if py_paths:
            suite = py_paths[0]
        repo_ref: Optional[str] = None
        repo_ref_params: List[str] = []
        candidates = [
            c
            for c in _DOTTED_RE.findall(oracle_text)
            # drop file names (majority.rs, sim.py): a citation, not a
            # symbol
            if c.rsplit(".", 1)[-1] not in ("rs", "cpp", "cc", "h", "go",
                                            "py", "md")
        ]
        for cand in candidates:
            hit = resolver.resolve_dotted(cand)
            if hit is not None:
                repo_ref, _, repo_ref_params = hit
                break
        if repo_ref is None:
            # Bare class names (HealthOracle, ScalarCluster) resolve
            # against the simref oracle module.
            for word in re.findall(r"\b[A-Z][A-Za-z0-9]+\b", oracle_text):
                hit = resolver.resolve_in(
                    "raft_tpu/multiraft/simref.py", [word]
                )
                if hit is not None:
                    repo_ref, _, repo_ref_params = hit
                    break
        rotted: Optional[str] = None
        if entry is not None and repo_ref is None:
            # a dotted candidate that LOOKS like a repo symbol but resolves
            # nowhere is oracle rot
            for cand in candidates:
                root = cand.split(".")[0]
                if root in ("quorum", "tracker", "raft_tpu", "simref", "util"):
                    rotted = cand
                    break
        if rotted is not None:
            violations.append(
                _v(
                    sf,
                    func.lineno,
                    f"kernel `{name}`'s oracle symbol `{rotted}` does not "
                    "resolve in the repo tree",
                )
            )
        elif (
            entry is not None
            and repo_ref is None
            and not py_paths
            and not cite
        ):
            violations.append(
                _v(
                    sf,
                    func.lineno,
                    f"kernel `{name}`'s parity-map entry has no "
                    "machine-checkable oracle: no repo symbol resolves, no "
                    "parity-suite file is named, no reference citation",
                )
            )
        suite_path = ctx.repo_root / suite
        if entry is not None and not suite_path.is_file():
            violations.append(
                _v(
                    sf,
                    func.lineno,
                    f"kernel `{name}`'s parity suite `{suite}` does not "
                    "exist",
                )
            )
        obligations.append(
            {
                "kernel": name,
                "params": [a.arg for a in func.args.args],
                "oracle": oracle_text or None,
                "repo_ref": repo_ref,
                "repo_ref_params": repo_ref_params,
                "reference_cite": cite,
                "parity_suite": suite,
                "tests": tests.get(name, []),
            }
        )
    document: Dict[str, object] = {
        "version": 1,
        "source": "raft_tpu/multiraft/kernels.py",
        "obligations": obligations,
    }
    return document, violations


def render(document: Dict[str, object]) -> str:
    return json.dumps(document, indent=2, sort_keys=True) + "\n"


def check_baseline(
    sf: SourceFile, ctx: Context, document: Dict[str, object]
) -> Iterator[Violation]:
    baseline = ctx.repo_root / BASELINE_RELPATH
    if not baseline.is_file():
        return  # fixtures / fresh trees: --emit-obligations creates it
    try:
        committed = json.loads(baseline.read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError):
        yield _v(
            sf,
            1,
            f"{BASELINE_RELPATH} is unreadable or not JSON; regenerate it "
            "with `python -m tools.graftcheck --emit-obligations "
            f"{BASELINE_RELPATH}`",
        )
        return
    if committed != document:
        got = {o["kernel"] for o in document.get("obligations", [])}  # type: ignore[union-attr]
        want = {o["kernel"] for o in committed.get("obligations", [])}
        dropped = sorted(want - got)
        added = sorted(got - want)
        detail = []
        if dropped:
            detail.append(f"dropped: {', '.join(dropped)}")
        if added:
            detail.append(f"new: {', '.join(added)}")
        yield _v(
            sf,
            1,
            "parity obligations drifted from the committed baseline "
            f"{BASELINE_RELPATH}"
            + (f" ({'; '.join(detail)})" if detail else " (entry contents changed)")
            + "; review the diff and regenerate with --emit-obligations",
        )
