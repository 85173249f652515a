#!/usr/bin/env python
"""Docs drift guards: fail when the docs and the code disagree.

Checks (each also run as a tier-1 test via tests/test_docs.py):

  1. PROTOCOL.md's control-op table == the op registry
     `repro.core.control.CTRL_OPS` (op names, direction, blocking kind).
  2. PROTOCOL.md's frame-format v2 table == the normative layout
     `repro.comm.transport.tcp.FRAME_V2_LAYOUT` (field names, sizes,
     types), plus the wire version and the MANA_WIRE_V1 escape hatch
     are documented.
  3. README's "Example flags" table == the actual argparse surface of
     examples/multirank_simulation.py (and the example's generated
     epilog lists every flag).
  4. docs/quickstart.sh's commands all appear verbatim in the README —
     the quickstart is the README's run instructions in executable
     form, so the README cannot document commands CI never runs.
  5. PROTOCOL.md's image-container-fields table == the registry
     `repro.core.codec.IMAGE_FIELDS` (ISSUE 6: the `n_ranks` and
     `remap` fields the elastic restore path depends on stay
     documented in lockstep with the code).
  6. PROTOCOL.md's store-manifest-fields table == the registry
     `repro.core.image_store.MANIFEST_FIELDS`, plus the current
     MANIFEST_FORMAT is stated (ISSUE 10: the durable store's commit
     record cannot drift from the docs).
  7. PERF.md's span table == the registry `repro.core.tracing.SPANS`
     (span names, where each is opened, the counters taken inside it).

Usage:  python docs/check_docs_drift.py   (exit 1 on any drift)
"""
from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "examples"))


def _read(*parts: str) -> str:
    with open(os.path.join(ROOT, *parts)) as f:
        return f.read()


def _md_table_rows(text: str, anchor: str):
    """Yield the cell lists of the first markdown table after `anchor`."""
    lines = text[text.index(anchor):].splitlines()
    in_table = False
    for line in lines:
        if line.startswith("|"):
            cells = [c.strip() for c in line.strip("|\n").split("|")]
            if set(cells[0]) <= {"-", " ", ":"}:  # separator row
                continue
            in_table = True
            yield cells
        elif in_table:
            return


def check_protocol_op_table() -> list:
    """PROTOCOL.md op table vs repro.core.control.CTRL_OPS."""
    from repro.core.control import CTRL_OPS
    errors = []
    doc = {}
    for cells in _md_table_rows(_read("docs", "PROTOCOL.md"),
                                "## Control ops"):
        m = re.match(r"`([a-z_]+)`", cells[0])
        if not m:
            continue  # header row
        doc[m.group(1)] = {"dir": cells[1],
                           "blocking": cells[2] == "blocking"}
    for op in sorted(set(CTRL_OPS) - set(doc)):
        errors.append(f"PROTOCOL.md op table is missing op {op!r} "
                      f"(present in control.CTRL_OPS)")
    for op in sorted(set(doc) - set(CTRL_OPS)):
        errors.append(f"PROTOCOL.md documents unknown op {op!r} "
                      f"(absent from control.CTRL_OPS)")
    for op in sorted(set(doc) & set(CTRL_OPS)):
        if doc[op]["blocking"] != CTRL_OPS[op]["blocking"]:
            errors.append(
                f"PROTOCOL.md kind for {op!r} disagrees with the "
                f"registry (registry blocking="
                f"{CTRL_OPS[op]['blocking']})")
        if doc[op]["dir"] != CTRL_OPS[op]["dir"]:
            errors.append(
                f"PROTOCOL.md direction for {op!r} is {doc[op]['dir']!r},"
                f" registry says {CTRL_OPS[op]['dir']!r}")
    return errors


def check_frame_format_table() -> list:
    """PROTOCOL.md frame-v2 table vs tcp.FRAME_V2_LAYOUT."""
    from repro.comm.transport.tcp import FRAME_V2_LAYOUT, WIRE_VERSION
    errors = []
    text = _read("docs", "PROTOCOL.md")
    anchor = "## Frame format v2"
    if anchor not in text:
        return [f"PROTOCOL.md is missing the {anchor!r} section"]
    doc = {}
    for cells in _md_table_rows(text, anchor):
        m = re.match(r"`([a-z]+)`", cells[0])
        if not m:
            continue
        doc[m.group(1)] = {"bytes": cells[1], "type": cells[2]}
    layout = {name: (size, typ) for name, size, typ, _ in FRAME_V2_LAYOUT}
    for f in sorted(set(layout) - set(doc)):
        errors.append(f"PROTOCOL.md frame table is missing field {f!r} "
                      f"(present in tcp.FRAME_V2_LAYOUT)")
    for f in sorted(set(doc) - set(layout)):
        errors.append(f"PROTOCOL.md frame table documents unknown "
                      f"field {f!r}")
    for f in sorted(set(doc) & set(layout)):
        size, typ = layout[f]
        want = "—" if size is None else str(size)
        if doc[f]["bytes"] != want:
            errors.append(f"PROTOCOL.md frame field {f!r} size is "
                          f"{doc[f]['bytes']!r}, layout says {want!r}")
        if doc[f]["type"] != typ:
            errors.append(f"PROTOCOL.md frame field {f!r} type is "
                          f"{doc[f]['type']!r}, layout says {typ!r}")
    section = text[text.index(anchor):]
    section = section[:section.index("\n## ") if "\n## " in section[4:]
                      else len(section)]
    if f"tcp.WIRE_VERSION = {WIRE_VERSION}" not in section:
        errors.append("PROTOCOL.md frame section does not state the "
                      f"current wire version ({WIRE_VERSION})")
    if "MANA_WIRE_V1" not in section:
        errors.append("PROTOCOL.md frame section does not document the "
                      "MANA_WIRE_V1 escape hatch")
    return errors


def check_image_container_fields() -> list:
    """PROTOCOL.md image-container table vs repro.core.codec.IMAGE_FIELDS."""
    from repro.core.codec import IMAGE_FIELDS
    errors = []
    text = _read("docs", "PROTOCOL.md")
    anchor = "## Image container fields"
    if anchor not in text:
        return [f"PROTOCOL.md is missing the {anchor!r} section"]
    doc = set()
    for cells in _md_table_rows(text, anchor):
        m = re.match(r"`([a-z_]+)`", cells[0])
        if m:
            doc.add(m.group(1))
    for f in sorted(set(IMAGE_FIELDS) - doc):
        errors.append(f"PROTOCOL.md image-container table is missing "
                      f"field {f!r} (present in codec.IMAGE_FIELDS)")
    for f in sorted(doc - set(IMAGE_FIELDS)):
        errors.append(f"PROTOCOL.md documents unknown image field {f!r} "
                      f"(absent from codec.IMAGE_FIELDS)")
    return errors


def check_manifest_fields() -> list:
    """PROTOCOL.md manifest table vs repro.core.image_store
    MANIFEST_FIELDS (ISSUE 10: the durable store's commit record)."""
    from repro.core.image_store import MANIFEST_FIELDS, MANIFEST_FORMAT
    errors = []
    text = _read("docs", "PROTOCOL.md")
    anchor = "## Store manifest fields"
    if anchor not in text:
        return [f"PROTOCOL.md is missing the {anchor!r} section"]
    doc = set()
    for cells in _md_table_rows(text, anchor):
        m = re.match(r"`([a-z_]+)`", cells[0])
        if m:
            doc.add(m.group(1))
    for f in sorted(set(MANIFEST_FIELDS) - doc):
        errors.append(f"PROTOCOL.md manifest table is missing field "
                      f"{f!r} (present in image_store.MANIFEST_FIELDS)")
    for f in sorted(doc - set(MANIFEST_FIELDS)):
        errors.append(f"PROTOCOL.md documents unknown manifest field "
                      f"{f!r} (absent from image_store.MANIFEST_FIELDS)")
    section = text[text.index(anchor):]
    section = section[:section.index("\n## ") if "\n## " in section[4:]
                      else len(section)]
    if f"MANIFEST_FORMAT = {MANIFEST_FORMAT}" not in section:
        errors.append("PROTOCOL.md manifest section does not state the "
                      f"current manifest format ({MANIFEST_FORMAT})")
    return errors


def check_span_table() -> list:
    """PERF.md's span table vs repro.core.tracing.SPANS."""
    from repro.core.tracing import SPANS
    errors = []
    text = _read("PERF.md")
    anchor = "### Spans and counters"
    if anchor not in text:
        return [f"PERF.md is missing the {anchor!r} table"]
    doc = {}
    for cells in _md_table_rows(text, anchor):
        m = re.fullmatch(r"`([a-z0-9_.]+)`", cells[0])
        if m:
            doc[m.group(1)] = (cells[1].strip("`"),
                               tuple(re.findall(r"`([a-z0-9_]+)`",
                                                cells[2])))
    for name in sorted(set(SPANS) - set(doc)):
        errors.append(f"PERF.md span table is missing span {name!r} "
                      f"(present in tracing.SPANS)")
    for name in sorted(set(doc) - set(SPANS)):
        errors.append(f"PERF.md documents unknown span {name!r} "
                      f"(absent from tracing.SPANS)")
    for name in sorted(set(doc) & set(SPANS)):
        if doc[name] != SPANS[name]:
            errors.append(f"PERF.md span {name!r} reads {doc[name]}, "
                          f"tracing.SPANS has {SPANS[name]}")
    return errors


def check_example_flags() -> list:
    """README 'Example flags' table + example epilog vs the parser."""
    import multirank_simulation as sim
    errors = []
    parser = sim.build_parser()
    flags = {s for a in parser._actions for s in a.option_strings
             if s.startswith("--") and s != "--help"}
    doc_flags = set()
    for cells in _md_table_rows(_read("README.md"), "## Example flags"):
        m = re.match(r"`(--[a-z-]+)`", cells[0])
        if m:
            doc_flags.add(m.group(1))
    for f in sorted(flags - doc_flags):
        errors.append(f"README 'Example flags' table is missing {f} "
                      f"(present in the example's argparse)")
    for f in sorted(doc_flags - flags):
        errors.append(f"README documents flag {f} that the example "
                      f"no longer has")
    epilog = parser.epilog or ""
    for f in sorted(flags):
        if f not in epilog:
            errors.append(f"example --help epilog is missing {f}")
    return errors


def check_quickstart_in_readme() -> list:
    """Every quickstart.sh command line appears verbatim in the README."""
    errors = []
    readme = re.sub(r"[ \t]+", " ", _read("README.md").replace("\\\n", " "))
    script = _read("docs", "quickstart.sh")
    for line in script.splitlines():
        line = line.strip().rstrip("\\").strip()
        if (not line or line.startswith("#") or line.startswith("set ")
                or line.startswith("cd ") or line.startswith("export ")
                or line == "fi" or line.startswith("if ")):
            continue
        if re.sub(r"[ \t]+", " ", line) not in readme:
            errors.append(f"quickstart.sh command not found in README: "
                          f"{line!r}")
    return errors


def check_architecture_linked() -> list:
    errors = []
    if not os.path.exists(os.path.join(ROOT, "docs", "ARCHITECTURE.md")):
        errors.append("docs/ARCHITECTURE.md is missing")
    readme = _read("README.md")
    for doc in ("docs/ARCHITECTURE.md", "docs/PROTOCOL.md"):
        if doc not in readme:
            errors.append(f"README does not link {doc}")
    return errors


CHECKS = (check_protocol_op_table, check_frame_format_table,
          check_image_container_fields, check_manifest_fields,
          check_span_table, check_example_flags, check_quickstart_in_readme,
          check_architecture_linked)


def main() -> int:
    failures = []
    for check in CHECKS:
        failures.extend(check())
    for f in failures:
        print(f"DRIFT: {f}", file=sys.stderr)
    if not failures:
        print("docs drift guards: OK")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
