"""Reading a compiled program's text (`compiled.as_text()`) in tests."""
import re


def while_body_all_reduces(text: str) -> dict:
    """{computation that is some while's body: the result types of the
    all-reduces it holds, in order}; bodies with none are left out."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", line)
        if head:
            name = head.group(1)
        got = re.search(r"= (\S+) all-reduce(?:-start)?\(", line)
        if got and name in bodies:
            out.setdefault(name, []).append(got.group(1))
    return out


def residual_reduces(text: str, residual: str) -> list:
    """How many all-reduces whose result type starts with `residual`
    (a regex, e.g. r"bf16\\[16,1024,2048\\]") each while body holds,
    fewest first, bodies with none left out."""
    counts = sorted(sum(1 for t in types if re.match(residual, t))
                    for types in while_body_all_reduces(text).values())
    return [c for c in counts if c]
