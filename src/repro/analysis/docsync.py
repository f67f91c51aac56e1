"""wire-spec-drift: docs/ARCHITECTURE.md and docs/OBSERVABILITY.md are
*normative* — this rule re-parses their tables on every run and diffs
them against what the code actually does, so the spec and the
implementation cannot drift apart silently.

Four contracts are diffed:

* the ``"__w"`` wire-tag table (ARCHITECTURE §3.3) vs the tags built by
  ``_to_wire`` and matched by ``_from_wire`` in ``sim/mailbox.py``;
* the FFLY container version sentence (ARCHITECTURE §3.2) vs
  ``VERSION`` / ``READABLE_VERSIONS`` in ``runtime/serialization.py``;
* every ``{"type": ...}`` message literal in the protocol sections vs
  the message dicts constructed in code;
* the instrumented-name table (OBSERVABILITY) vs every
  ``obs.span/count/gauge/observe`` call with a constant name.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

from repro.analysis.core import Finding, Project, Rule, dotted_name

_TAG_ROW = re.compile(r'^\|\s*`"(\w+)"`')
_VERSION_SENT = re.compile(
    r"Current version is (\d+); readers accept ([0-9,\s]+(?:and\s+\d+)?)")
_MSG_TYPE = re.compile(r'\{"type":\s*"(\w+)"')
_NAME_TOKEN = re.compile(r"`([^`]+)`")

#: obs call attribute -> kind word used in the doc table
_OBS_KINDS = {"span": "span", "record": "span", "count": "counter",
              "gauge": "gauge", "observe": "hist"}


# ---------------------------------------------------------------------------
# doc-side parsers
# ---------------------------------------------------------------------------

def parse_tag_table(doc: str) -> Dict[str, int]:
    """``{"none": line, "kind": line, ...}`` from the §3.3 table."""
    out: Dict[str, int] = {}
    for i, line in enumerate(doc.splitlines(), start=1):
        m = _TAG_ROW.match(line)
        if m:
            out.setdefault(m.group(1), i)
    return out

def parse_versions(doc: str) -> Optional[Tuple[int, Set[int], int]]:
    """(current, readable, line) from the §3.2 version sentence."""
    for i, line in enumerate(doc.splitlines(), start=1):
        m = _VERSION_SENT.search(line)
        if m:
            readable = {int(n) for n in re.findall(r"\d+", m.group(2))}
            return int(m.group(1)), readable, i
    return None

def parse_message_types(doc: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for i, line in enumerate(doc.splitlines(), start=1):
        for m in _MSG_TYPE.finditer(line):
            out.setdefault(m.group(1), i)
    return out

def _expand_name_cell(cell: str) -> List[str]:
    """Expand one name cell: ``wire.frames_in/out`` alternates the last
    underscore segment; ``mig.pack`` / ``mig.transfer`` are separate
    backtick tokens, each a full name."""
    names: List[str] = []
    for token in _NAME_TOKEN.findall(cell):
        parts = token.split("/")
        prev = parts[0].strip()
        names.append(prev)
        for frag in parts[1:]:
            frag = frag.strip()
            if "." in frag:
                prev = frag
            elif "_" in prev:
                prev = prev.rsplit("_", 1)[0] + "_" + frag
            else:
                prev = prev.rsplit(".", 1)[0] + "." + frag
            names.append(prev)
    return names

def parse_obs_table(doc: str) -> Dict[str, Tuple[str, int]]:
    """``{name: (kind, line)}`` from the 'What is instrumented' table."""
    out: Dict[str, Tuple[str, int]] = {}
    in_section = False
    for i, line in enumerate(doc.splitlines(), start=1):
        if line.startswith("## "):
            in_section = line.strip() == "## What is instrumented"
            continue
        if not in_section or not line.startswith("|"):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if len(cells) < 2 or cells[0] in ("Name", "") \
                or set(cells[0]) <= {"-", " "}:
            continue
        kind = cells[1]
        for name in _expand_name_cell(cells[0]):
            out.setdefault(name, (kind, i))
    return out


# ---------------------------------------------------------------------------
# code-side extractors
# ---------------------------------------------------------------------------

def _code_tags(project: Project) -> Tuple[Dict[str, int], Dict[str, int]]:
    """(encode tags from ``{_TAG: "x", ...}`` literals, decode tags from
    ``tag == "x"`` compares) -> first line each."""
    enc: Dict[str, int] = {}
    dec: Dict[str, int] = {}
    for pf in project.files_under(project.config["wire_tag_files"]):
        if pf.tree is None:
            continue
        for node in ast.walk(pf.tree):
            if isinstance(node, ast.Dict):
                for k, v in zip(node.keys, node.values):
                    is_tag_key = (
                        isinstance(k, ast.Name) and k.id == "_TAG") or (
                        isinstance(k, ast.Constant) and k.value == "__w")
                    if is_tag_key and isinstance(v, ast.Constant) \
                            and isinstance(v.value, str):
                        enc.setdefault(v.value, node.lineno)
            elif isinstance(node, ast.Compare) \
                    and isinstance(node.left, ast.Name) \
                    and node.left.id == "tag" \
                    and len(node.comparators) == 1 \
                    and isinstance(node.comparators[0], ast.Constant) \
                    and isinstance(node.comparators[0].value, str):
                dec.setdefault(node.comparators[0].value, node.lineno)
    return enc, dec

def _code_versions(project: Project) -> Optional[
        Tuple[int, Set[int], str, int]]:
    rel = project.config["serialization_file"]
    pf = project.py.get(rel)
    if pf is None or pf.tree is None:
        return None
    current: Optional[int] = None
    readable: Set[int] = set()
    line = 1
    for node in ast.walk(pf.tree):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if not isinstance(tgt, ast.Name):
                continue
            if tgt.id == "VERSION" and isinstance(node.value, ast.Constant):
                current, line = node.value.value, node.lineno
            elif tgt.id == "READABLE_VERSIONS" and isinstance(
                    node.value, (ast.Tuple, ast.List, ast.Set)):
                readable = {e.value for e in node.value.elts
                            if isinstance(e, ast.Constant)}
    if current is None:
        return None
    return current, readable, rel, line

def _code_message_types(project: Project) -> Dict[str, Tuple[str, int]]:
    out: Dict[str, Tuple[str, int]] = {}
    for pf in project.files_under(project.config["wire_message_files"]):
        if pf.tree is None:
            continue
        for node in ast.walk(pf.tree):
            if not isinstance(node, ast.Dict):
                continue
            for k, v in zip(node.keys, node.values):
                if isinstance(k, ast.Constant) and k.value == "type" \
                        and isinstance(v, ast.Constant) \
                        and isinstance(v.value, str):
                    out.setdefault(v.value, (pf.path, node.lineno))
    return out

def _code_obs_names(project: Project) -> Dict[str, Tuple[str, str, int]]:
    """``{name: (kind, path, line)}`` from obs.* calls with constant
    names. Only receivers named ``obs``/``telemetry`` count, and inside
    the plane itself (``src/repro/obs/``) the bare calls with which it
    records on its own (the GC hook's ``record``)."""
    out: Dict[str, Tuple[str, str, int]] = {}
    for pf in project.files_under(project.config["obs_scope"]):
        if pf.tree is None:
            continue
        plane = pf.path.startswith("src/repro/obs/")
        for node in ast.walk(pf.tree):
            if not isinstance(node, ast.Call):
                continue
            if plane:
                if not (isinstance(node.func, ast.Name)
                        and node.func.id in _OBS_KINDS):
                    continue
                kind = _OBS_KINDS[node.func.id]
            else:
                if not (isinstance(node.func, ast.Attribute)
                        and node.func.attr in _OBS_KINDS):
                    continue
                recv = dotted_name(node.func.value)
                if recv is None \
                        or recv.split(".")[-1] not in ("obs", "telemetry"):
                    continue
                kind = _OBS_KINDS[node.func.attr]
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                out.setdefault(node.args[0].value,
                               (kind, pf.path, node.lineno))
    return out


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------

class WireSpecDrift(Rule):
    name = "wire-spec-drift"
    contract = ("ARCHITECTURE.md's tag/version/message tables and "
                "OBSERVABILITY.md's instrumented-name table are "
                "normative; the code must match them exactly")

    def run(self, project: Project) -> Iterator[Finding]:
        arch_rel = project.config["architecture_doc"]
        obs_rel = project.config["observability_doc"]
        arch = project.read_text(arch_rel)
        obsdoc = project.read_text(obs_rel)
        if arch is None:
            yield Finding(self.name, arch_rel, 0,
                          "architecture doc is missing — the wire spec "
                          "has no normative source to diff against")
        else:
            yield from self._diff_tags(project, arch, arch_rel)
            yield from self._diff_versions(project, arch, arch_rel)
            yield from self._diff_messages(project, arch, arch_rel)
        if obsdoc is None:
            yield Finding(self.name, obs_rel, 0,
                          "observability doc is missing — instrumented "
                          "names have no normative table to diff against")
        else:
            yield from self._diff_obs(project, obsdoc, obs_rel)

    def _diff_tags(self, project, arch, arch_rel) -> Iterator[Finding]:
        doc_tags = parse_tag_table(arch)
        enc, dec = _code_tags(project)
        tag_file = (project.config["wire_tag_files"] or [arch_rel])[0]
        if not doc_tags:
            yield Finding(self.name, arch_rel, 0,
                          "no wire-tag table rows found in §3.3 — the "
                          "drift check cannot see the spec")
            return
        for tag in sorted(set(enc) | set(dec)):
            if tag not in doc_tags:
                line = enc.get(tag) or dec.get(tag)
                yield Finding(
                    self.name, tag_file, line,
                    f'wire tag "{tag}" is handled in code but missing '
                    f"from the §3.3 table in {arch_rel}")
        for tag, line in sorted(doc_tags.items()):
            if tag not in enc:
                yield Finding(
                    self.name, arch_rel, line,
                    f'documented wire tag "{tag}" is never produced by '
                    "_to_wire")
            if tag not in dec:
                yield Finding(
                    self.name, arch_rel, line,
                    f'documented wire tag "{tag}" is never matched by '
                    "_from_wire")

    def _diff_versions(self, project, arch, arch_rel) -> Iterator[Finding]:
        doc = parse_versions(arch)
        code = _code_versions(project)
        if doc is None:
            yield Finding(self.name, arch_rel, 0,
                          "no 'Current version is N; readers accept ...' "
                          "sentence found in the container spec")
            return
        if code is None:
            yield Finding(
                self.name, project.config["serialization_file"], 0,
                "VERSION / READABLE_VERSIONS constants not found in the "
                "serialization module")
            return
        doc_cur, doc_read, doc_line = doc
        code_cur, code_read, rel, line = code
        if doc_cur != code_cur:
            yield Finding(
                self.name, rel, line,
                f"FFLY writer VERSION={code_cur} but {arch_rel} says "
                f"current version is {doc_cur}")
        if doc_read != code_read:
            yield Finding(
                self.name, rel, line,
                f"READABLE_VERSIONS={sorted(code_read)} but {arch_rel} "
                f"says readers accept {sorted(doc_read)}")

    def _diff_messages(self, project, arch, arch_rel) -> Iterator[Finding]:
        doc_types = parse_message_types(arch)
        code_types = _code_message_types(project)
        for t, (path, line) in sorted(code_types.items()):
            if t not in doc_types:
                yield Finding(
                    self.name, path, line,
                    f'message type "{t}" is constructed in code but '
                    f"appears nowhere in {arch_rel}'s protocol sections")
        for t, line in sorted(doc_types.items()):
            if t not in code_types:
                yield Finding(
                    self.name, arch_rel, line,
                    f'documented message type "{t}" is never constructed '
                    "by any wire-message file")

    def _diff_obs(self, project, obsdoc, obs_rel) -> Iterator[Finding]:
        doc_names = parse_obs_table(obsdoc)
        code_names = _code_obs_names(project)
        if not doc_names:
            yield Finding(self.name, obs_rel, 0,
                          "no rows found in the 'What is instrumented' "
                          "table — the drift check cannot see the spec")
            return
        for name, (kind, path, line) in sorted(code_names.items()):
            if name not in doc_names:
                yield Finding(
                    self.name, path, line,
                    f'instrumented name "{name}" ({kind}) is missing '
                    f"from the table in {obs_rel}")
            elif doc_names[name][0] != kind:
                yield Finding(
                    self.name, path, line,
                    f'"{name}" is emitted as a {kind} but {obs_rel} '
                    f"documents it as a {doc_names[name][0]}")
        for name, (kind, line) in sorted(doc_names.items()):
            if name not in code_names:
                yield Finding(
                    self.name, obs_rel, line,
                    f'documented instrumented name "{name}" ({kind}) is '
                    "never emitted by any obs call in the source tree")
