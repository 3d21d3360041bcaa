"""Docs CI: fenced code blocks must parse; referenced repo paths must exist.

Checks, over README.md and docs/*.md:

  1. every ```python block compiles (`compile(..., "exec")` — the same
     bar `python -m compileall` sets, without importing anything);
  2. every ```bash / ```sh block tokenizes line-by-line with shlex
     (continuations joined, comments skipped), and any `python -m <mod>`
     module rooted in this repo (`repro.*` via src/, `benchmarks.*`)
     resolves to a file or package in the tree;
  3. every intra-repo path the prose references — tokens starting with
     src/, docs/, examples/, benchmarks/, scripts/, tests/ or .github/ —
     exists (globs must match at least one file);
  4. every backticked module reference resolves in the tree: dotted
     `repro.*` / `benchmarks.*` modules through the same resolver as
     `python -m`, and `src/repro`-relative prose refs like
     `runtime/sharded_serve.py` or `graph/shard.py` against src/repro/.

Exit nonzero listing every failure:  python scripts/check_docs.py
"""

from __future__ import annotations

import glob
import os
import re
import shlex
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC_FILES = ["README.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)
PATH_RE = re.compile(r"(?:src|docs|examples|benchmarks|scripts|tests|\.github)/[\w./*-]+")
FENCE_RE = re.compile(r"^```(\w*)\s*$")
MODULE_RE = re.compile(r"python\s+(?:-\S+\s+)*-m\s+([A-Za-z_][\w.]*)")
# Backticked prose references: `repro.runtime.sharded_serve` (dotted) and
# `runtime/sharded_serve.py` (src/repro-relative, top-level package dirs).
DOTTED_REF_RE = re.compile(r"`((?:repro|benchmarks)(?:\.\w+)+)`")
SRC_REL_RE = re.compile(r"`((?:core|graph|runtime|launch|models|utils)/[\w/]+\.py)`")


def code_blocks(text: str):
    """Yield (language, source, first_line_number) for every fenced block."""
    lang, buf, start = None, [], 0
    for ln, line in enumerate(text.splitlines(), 1):
        m = FENCE_RE.match(line.strip()) if line.strip().startswith("```") else None
        if m and lang is None:
            lang, buf, start = m.group(1) or "", [], ln
        elif line.strip() == "```" and lang is not None:
            yield lang, "\n".join(buf), start
            lang = None
        elif lang is not None:
            buf.append(line)


def module_exists(mod: str) -> bool:
    """Resolve a repo-rooted dotted module to a file/package in the tree."""
    parts = mod.split(".")
    roots = {"repro": "src", "benchmarks": ""}
    if parts[0] not in roots:
        return True  # external tool (pytest, pip, ...) — not ours to check
    rel = os.path.join(roots[parts[0]], *parts)
    return os.path.isfile(os.path.join(REPO, rel + ".py")) or os.path.isdir(
        os.path.join(REPO, rel)
    )


def check_file(relpath: str) -> list[str]:
    errors: list[str] = []
    with open(os.path.join(REPO, relpath)) as f:
        text = f.read()

    for lang, src, ln in code_blocks(text):
        where = f"{relpath}:{ln}"
        if lang == "python":
            try:
                compile(src, where, "exec")
            except SyntaxError as e:
                errors.append(f"{where}: python block does not compile: {e}")
        elif lang in ("bash", "sh", "shell"):
            joined = src.replace("\\\n", " ")
            for cmd in joined.splitlines():
                cmd = cmd.strip()
                if not cmd or cmd.startswith("#"):
                    continue
                try:
                    shlex.split(cmd)
                except ValueError as e:
                    errors.append(f"{where}: bash line does not tokenize ({cmd!r}): {e}")
            for mod in MODULE_RE.findall(joined):
                if not module_exists(mod):
                    errors.append(f"{where}: `python -m {mod}` does not resolve in the tree")

    for mod in sorted(set(DOTTED_REF_RE.findall(text))):
        # a ref may name an attribute (`repro.core.trace.Tracer`): the
        # module prefix resolving is what we can check statically
        if not (module_exists(mod) or module_exists(mod.rsplit(".", 1)[0])):
            errors.append(f"{relpath}: backticked module `{mod}` does not resolve")
    for ref in sorted(set(SRC_REL_RE.findall(text))):
        if not os.path.isfile(os.path.join(REPO, "src", "repro", ref)):
            errors.append(f"{relpath}: backticked ref `{ref}` not under src/repro/")

    for ref in sorted(set(PATH_RE.findall(text))):
        ref = ref.rstrip(".,;:")
        if "*" in ref:
            if not glob.glob(os.path.join(REPO, ref)):
                errors.append(f"{relpath}: glob `{ref}` matches nothing")
        elif not os.path.exists(os.path.join(REPO, ref)):
            errors.append(f"{relpath}: referenced path `{ref}` does not exist")
    return errors


def main() -> int:
    all_errors: list[str] = []
    for relpath in DOC_FILES:
        all_errors.extend(check_file(relpath))
    for e in all_errors:
        print(f"FAIL {e}")
    print(f"checked {len(DOC_FILES)} docs: {'OK' if not all_errors else f'{len(all_errors)} problem(s)'}")
    return 1 if all_errors else 0


if __name__ == "__main__":
    sys.exit(main())
