"""Reading HLO text in tests: the scope path of each gather, and the
conditional branch that runs it."""
import re

_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\b(?:branch_computations=\{([^}]*)\}|"
                       r"(?:true|false)_computation=%?([\w.\-]+))")
_GATHER = re.compile(r"\sgather\(.*op_name=\"([^\"]*)\"")


def computations(text: str) -> dict:
    """``{computation: its instruction lines}`` of HLO text."""
    out, cur = {}, None
    for line in text.splitlines():
        m = _HEAD.match(line)
        if m:
            cur = out.setdefault(m.group(1), [])
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            cur.append(line)
    return out


def scope_path(op_name: str, words) -> str:
    """The scope path of an ``op_name``: its segments from ``fabric`` on
    that are in ``words``, the primitive's own name (the last segment)
    left out, since ``gather`` is both."""
    seg = op_name.split("/")[:-1]
    if "fabric" not in seg:
        return ""
    seg = seg[seg.index("fabric"):]
    return "/".join(w for w in seg if w in words)


def scoped_gathers(text: str, words) -> list:
    """``(scope path, branch)`` of every gather in the HLO ``text``:
    ``branch`` is the conditional branch computation that runs it (through
    any fusions around it), ``None`` where no conditional does."""
    comps = computations(text)
    caller, branch = {}, set()
    for name, lines in comps.items():
        for line in lines:
            for m in _CALLS.finditer(line):
                caller.setdefault(m.group(1), name)
            for m in _BRANCHES.finditer(line):
                names = (m.group(1) or m.group(2)).split(",")
                branch |= {b.strip().lstrip("%") for b in names}
    out = []
    for name, lines in comps.items():
        for line in lines:
            m = _GATHER.search(line)
            if not m:
                continue
            comp = name
            while comp not in branch and comp in caller:
                comp = caller[comp]
            out.append((scope_path(m.group(1), words),
                        comp if comp in branch else None))
    return out
