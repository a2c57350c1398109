"""A reader for the subset of PQL the benchmark's traffic uses, kept
with the references so that they parse what was sent and import nothing
of the program: ``Name(arg, ...)`` where an argument is a nested call,
``key=value`` with an integer, a double-quoted string or a list of
integers (``ids=[1, 2, 3]``), or a condition ``field OP value`` with
``OP`` one of ``== != < <= > >=`` and an integer, or ``><`` and a pair
``[low, high]``. A condition is kept as ``args[field] = Cond(op, value)``,
the pair as a tuple."""
import collections
import re

_TOKEN = re.compile(r'\s*(?:([A-Za-z_][A-Za-z0-9_]*)|(-?\d+)|"([^"]*)"'
                    r'|(==|!=|<=|>=|><|<|>)|(.))')
NAME, INT, STRING, OP, PUNCT = 1, 2, 3, 4, 5
END = (PUNCT, "")

Cond = collections.namedtuple("Cond", "op value")


class Call:
    __slots__ = ("name", "children", "args")

    def __init__(self, name, children, args):
        self.name, self.children, self.args = name, children, args

    def __repr__(self):
        return f"Call({self.name!r}, {self.children!r}, {self.args!r})"


def parse(text):
    tokens = [(m.lastindex, m.group(m.lastindex))
              for m in _TOKEN.finditer(text) if m.group(0).strip()]
    # Input that stops short reads END, twice at the most, and is
    # refused there: a ValueError, not an index out of range.
    call, i = _call(tokens + [END, END], 0)
    if i != len(tokens):
        raise ValueError(f"trailing input in {text!r}")
    return call


def _call(tokens, i):
    kind, name = tokens[i]
    if kind != NAME or tokens[i + 1] != (PUNCT, "("):
        raise ValueError(f"expected a call at token {i}: {tokens[i]}")
    i += 2
    children, args = [], {}
    while tokens[i] != (PUNCT, ")"):
        if tokens[i] == (PUNCT, ","):
            i += 1
        elif tokens[i][0] == NAME and tokens[i + 1] == (PUNCT, "="):
            key = tokens[i][1]
            args[key], i = _value(tokens, i + 2, key)
        elif tokens[i][0] == NAME and tokens[i + 1][0] == OP:
            field, op = tokens[i][1], tokens[i + 1][1]
            value, i = _value(tokens, i + 2, field)
            pair = isinstance(value, list) and len(value) == 2
            if not (pair if op == "><" else isinstance(value, int)):
                raise ValueError(f"bad value for {field} {op}: {value!r}")
            args[field] = Cond(op, tuple(value) if pair else value)
        else:
            child, i = _call(tokens, i)
            children.append(child)
    return Call(name, children, args), i + 1


def _value(tokens, i, name):
    """An integer, a quoted string, or ``[int, ...]`` as a list."""
    kind, val = tokens[i]
    if kind == INT:
        return int(val), i + 1
    if kind == STRING:
        return val, i + 1
    if (kind, val) != (PUNCT, "["):
        raise ValueError(f"bad value for {name}: {val!r}")
    out, i = [], i + 1
    while tokens[i] != (PUNCT, "]"):
        if tokens[i][0] != INT:
            raise ValueError(f"bad list for {name}: {tokens[i][1]!r}")
        out.append(int(tokens[i][1]))
        i += 1
        if tokens[i] == (PUNCT, ","):
            i += 1
    return out, i + 1


def leaves(call):
    """Every ``Bitmap`` leaf of a call tree, left to right."""
    if call.name == "Bitmap":
        return [call]
    return [leaf for c in call.children for leaf in leaves(c)]


def conditions(call):
    """Every ``(frame, field, Cond)`` of a call tree, left to right as
    PQL writes a call: its children, then its own arguments. ``frame``
    is the ``frame=`` of the call that holds the condition, wherever in
    the call it stands; None where the call names none."""
    out = [c for child in call.children for c in conditions(child)]
    return out + [(call.args.get("frame"), field, v)
                  for field, v in call.args.items() if isinstance(v, Cond)]
