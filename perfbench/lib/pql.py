"""A reader for the subset of PQL the benchmark's traffic uses, kept
with the references so that they parse what was sent and import nothing
of the program: ``Name(arg, ...)`` where an argument is a nested call or
``key=value`` with an integer or a double-quoted string."""
import re

_TOKEN = re.compile(r'\s*(?:([A-Za-z_][A-Za-z0-9_]*)|(-?\d+)|"([^"]*)"|(.))')


class Call:
    __slots__ = ("name", "children", "args")

    def __init__(self, name, children, args):
        self.name, self.children, self.args = name, children, args

    def __repr__(self):
        return f"Call({self.name!r}, {self.children!r}, {self.args!r})"


def parse(text):
    tokens = [(m.lastindex, m.group(m.lastindex))
              for m in _TOKEN.finditer(text) if m.group(0).strip()]
    call, i = _call(tokens, 0)
    if i != len(tokens):
        raise ValueError(f"trailing input in {text!r}")
    return call


def _call(tokens, i):
    kind, name = tokens[i]
    if kind != 1 or tokens[i + 1] != (4, "("):
        raise ValueError(f"expected a call at token {i}: {tokens[i]}")
    i += 2
    children, args = [], {}
    while tokens[i] != (4, ")"):
        if tokens[i] == (4, ","):
            i += 1
            continue
        if tokens[i][0] == 1 and tokens[i + 1] == (4, "="):
            kind, val = tokens[i + 2]
            if kind == 2:
                val = int(val)
            elif kind != 3:
                raise ValueError(f"bad value for {tokens[i][1]}: {val!r}")
            args[tokens[i][1]] = val
            i += 3
        else:
            child, i = _call(tokens, i)
            children.append(child)
    return Call(name, children, args), i + 1


def leaves(call):
    """Every ``Bitmap`` leaf of a call tree, left to right."""
    if call.name == "Bitmap":
        return [call]
    return [leaf for c in call.children for leaf in leaves(c)]
