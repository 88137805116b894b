"""GC001 no-implicit-dtype.

Every jnp array constructor in the device modules must pass an explicit
dtype.  The batched backend's parity contract is "all planes are int32/bool"
(raft_tpu/multiraft/kernels.py); jnp's weak-typing rules otherwise promote
Python scalars platform- and context-dependently (int -> int32 vs int64
under x64, bool -> bool vs int32 after arithmetic), which is exactly the
class of silent divergence the scalar-vs-device parity suite cannot
localize.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..core import Context, Rule, SourceFile, Violation

# constructor -> number of positional args at which the dtype slot is filled
# (jnp signatures: zeros(shape, dtype), ones(shape, dtype),
#  full(shape, fill_value, dtype), arange(start, stop, step, dtype),
#  asarray(a, dtype), array(object, dtype))
_CTORS = {
    "zeros": 2,
    "ones": 2,
    "full": 3,
    "arange": 4,
    "asarray": 2,
    "array": 2,
}


class NoImplicitDtype(Rule):
    id = "GC001"
    slug = "no-implicit-dtype"
    doc = "jnp constructors in device modules must pass an explicit dtype"

    def applies(self, sf: SourceFile) -> bool:
        return sf.is_python and "raft_tpu/multiraft/" in sf.norm()

    def check(self, sf: SourceFile, ctx: Context) -> Iterator[Violation]:
        for node in ast.walk(sf.ast_tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (
                isinstance(fn, ast.Attribute)
                and fn.attr in _CTORS
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "jnp"
            ):
                continue
            if any(kw.arg == "dtype" for kw in node.keywords):
                continue
            if len(node.args) >= _CTORS[fn.attr]:
                continue  # dtype passed positionally
            yield Violation(
                sf.display_path,
                node.lineno,
                self.id,
                self.slug,
                f"jnp.{fn.attr}(...) without an explicit dtype; pass "
                "dtype=jnp.int32/bool/... (int32/bool weak-typing contract, "
                "kernels.py module docstring)",
            )
