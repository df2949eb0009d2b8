"""Reading ``compiled.as_text()`` in the tests that compile for a described chip: a
Pallas kernel's call, what feeds it and what takes its result. An instruction's name
is its own in the whole module, so a name finds its line."""

import re

_DEFINES = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_NAME = re.compile(r"%([\w.\-]+)")


def kernel_calls(text: str, kernel: str) -> list:
    """The lines that call the Pallas kernel ``kernel``."""
    return [line for line in text.splitlines()
            if "tpu_custom_call" in line and (m := _DEFINES.match(line)) and m.group(1).startswith(kernel)]


def name_of(line: str) -> str:
    return _DEFINES.match(line).group(1)


def operands(line: str) -> list:
    """The names in the instruction's argument list (the first parenthesis that opens
    on a name: a type's tiling, ``T(8,128)``, opens on a digit)."""
    args = re.search(r"\((%[^)]*)\)", line.split(" = ", 1)[1])
    return _NAME.findall(args.group(1)) if args else []


def users(text: str, name: str) -> list:
    """The lines that take ``name`` as an operand."""
    return [line for line in text.splitlines() if _DEFINES.match(line) and name in operands(line)]


def is_product_fusion(text: str, line: str) -> bool:
    """Whether ``line`` is a fusion whose computation holds a matrix product (which the
    TPU compiler writes as a convolution)."""
    called = re.search(r"fusion\(.*calls=%([\w.\-]+)", line)
    if not called:
        return False
    body = text.split(f"\n%{called.group(1)} (", 1)[1].split("\n}", 1)[0]
    return " convolution(" in body
