#!/usr/bin/env python3
"""Lint: every serving span kind must be asserted on by name in tests.

The serving plane emits a per-request span tree (queue_wait, admit,
prefill_chunk, first_token, decode samples, a terminal instant) plus
the flight-recorder snapshot instant. Dashboards, the trace merger,
and the TTFT-attribution tests all key on these literal names — a
kind that can be renamed or dropped without failing a test is an
observability contract nobody is holding. So this lint walks the
SERVE_SPAN_KINDS tuple in engine.py, the SERVE_PHASE_KINDS tuple beside
it (the loop-thread phases of utils/trace.py `phase`, which the
benchmark's readers key on) — and the FLEET_SPAN_KINDS tuple
in fleet.py, the cross-replica routing/migration/hedging events the
fleet stitches onto the same request tree — and fails unless each
name appears QUOTED on an assertion line (a code line containing
``assert``) in some tests/ file.

Run directly (exit 1 on violation) or via
tests/test_serve_observability.py, which keeps the lint itself in the
tier-1 suite:

    python tools/check_serve_spans.py [repo_root]
"""

from __future__ import annotations

import io
import os
import re
import sys
import tokenize

_KINDS_RE = re.compile(
    r"SERVE_SPAN_KINDS\s*=\s*\(([^)]*)\)", re.DOTALL)
_FLEET_KINDS_RE = re.compile(
    r"FLEET_SPAN_KINDS\s*=\s*\(([^)]*)\)", re.DOTALL)
_PHASE_KINDS_RE = re.compile(
    r"SERVE_PHASE_KINDS\s*=\s*\(([^)]*)\)", re.DOTALL)
_NAME_RE = re.compile(r"['\"]([A-Za-z0-9_.]+)['\"]")


def _declared(path: str, tuple_re) -> list:
    """The quoted names inside the tuple `tuple_re` finds in `path`;
    empty where the file has no such tuple."""
    with open(path, encoding="utf-8") as f:
        m = tuple_re.search(f.read())
    return _NAME_RE.findall(m.group(1)) if m else []


def span_kinds(engine_path: str) -> list:
    """Span-kind names declared in engine.py's SERVE_SPAN_KINDS."""
    return _declared(engine_path, _KINDS_RE)


def phase_kinds(engine_path: str) -> list:
    """Loop-phase names declared in engine.py's SERVE_PHASE_KINDS
    (utils/trace.py `phase`: what the serving loop thread is doing)."""
    return _declared(engine_path, _PHASE_KINDS_RE)


def fleet_span_kinds(fleet_path: str) -> list:
    """Span-kind names declared in fleet.py's FLEET_SPAN_KINDS — the
    cross-replica events (routing, migration, hedging) the fleet
    router stitches onto each request's trace tree."""
    return _declared(fleet_path, _FLEET_KINDS_RE)


def _code_lines(path: str):
    """Yield (lineno, source) for non-comment code lines. STRING tokens
    are KEPT (span kinds appear as string literals in tests); comments
    are dropped so a mention in prose doesn't count."""
    with open(path, "rb") as f:
        src = f.read()
    lines = {}
    try:
        for tok in tokenize.tokenize(io.BytesIO(src).readline):
            if tok.type in (tokenize.COMMENT, tokenize.ENCODING):
                continue
            lines.setdefault(tok.start[0], []).append(tok.string)
    except tokenize.TokenError:
        # fall back to raw lines; better a false positive than a skip
        for i, line in enumerate(src.decode("utf-8", "replace").split("\n")):
            lines.setdefault(i + 1, []).append(line)
    for no in sorted(lines):
        yield no, "".join(lines[no])


def file_asserts_kind(path: str, name: str) -> bool:
    """True when some assertion line in `path` names the kind quoted.
    A multi-line assert still counts: the tokenizer joins each logical
    token to its starting line, and the quoted name only has to share
    a line with the ``assert`` keyword — which is where trace-shape
    tests naturally put it (``assert "queue_wait" in kinds``)."""
    quoted = (f'"{name}"', f"'{name}'")
    for _no, code in _code_lines(path):
        if "assert" in code and any(q in code for q in quoted):
            return True
    return False


def _test_files(tests_dir: str) -> list:
    out = []
    for dirpath, _dirs, files in os.walk(tests_dir):
        for fname in sorted(files):
            if fname.startswith("test_") and fname.endswith(".py"):
                out.append(os.path.join(dirpath, fname))
    return out


def _unasserted(names: list, test_files: list) -> list:
    return [n for n in names
            if not any(file_asserts_kind(p, n) for p in test_files)]


def unasserted_kinds(engine_path: str, tests_dir: str) -> list:
    return _unasserted(span_kinds(engine_path), _test_files(tests_dir))


def unasserted_fleet_kinds(fleet_path: str, tests_dir: str) -> list:
    return _unasserted(fleet_span_kinds(fleet_path),
                       _test_files(tests_dir))


def main(argv) -> int:
    root = argv[1] if len(argv) > 1 else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    engine_path = os.path.join(root, "kubeml_tpu", "serve", "engine.py")
    fleet_path = os.path.join(root, "kubeml_tpu", "serve", "fleet.py")
    tests_dir = os.path.join(root, "tests")
    names = span_kinds(engine_path)
    if not names:
        print(f"{engine_path}: no SERVE_SPAN_KINDS found — lint is "
              "miswired", file=sys.stderr)
        return 1
    missing = unasserted_kinds(engine_path, tests_dir)
    missing += _unasserted(phase_kinds(engine_path),
                           _test_files(tests_dir))
    registries = "kubeml_tpu/serve/engine.py SERVE_SPAN_KINDS / " \
        "SERVE_PHASE_KINDS"
    # fleet registry: same contract, separate tuple. A tree without
    # fleet.py (the lint's own self-test fixtures) only checks the
    # engine registry; a tree WITH fleet.py but no tuple is miswired.
    if os.path.exists(fleet_path):
        if not fleet_span_kinds(fleet_path):
            print(f"{fleet_path}: no FLEET_SPAN_KINDS found — lint is "
                  "miswired", file=sys.stderr)
            return 1
        missing += unasserted_fleet_kinds(fleet_path, tests_dir)
        registries += " / fleet.py FLEET_SPAN_KINDS"
    for n in missing:
        print(f"serving span kind {n!r} is unasserted: no tests/ file "
              f"carries an assert line naming it quoted", file=sys.stderr)
    if missing:
        print(f"\n{len(missing)} unasserted span kind"
              f"{'' if len(missing) == 1 else 's'}: every name in "
              f"{registries} needs a quoted-name assertion in tests/",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
