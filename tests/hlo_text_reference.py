"""The counting rule's form of a module (`observability.profiling`:
{computation: [(name, opcode, kind, extents, windows, calls), ...]}) read
from compiled HLO text: the independent check of the `hlo.proto` fields the
reducer declares by hand (`_HLO_FIELDS`), whose front end reads the module
the profiler stores in a trace. On the deviceless v5e compile of every
decoder step the two agree on every instruction, to the operation
(tests/test_tpu_compile.py); tests/test_trace_reducer.py counts a traced
CPU step both ways. Not a test, and nothing of the program reads it."""
import re
from typing import Dict, List, Tuple

from se3_transformer_tpu.observability.profiling import PRODUCT_OPCODES

_TEXT_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*')
_TEXT_HEADER = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$')
_TEXT_DIMS = re.compile(r'^\w+\[([^\]]*)\]')
_TEXT_CALLS = re.compile(r'\bcalls=%?([\w.\-]+)')
_TEXT_KIND = re.compile(r'\bkind=k(\w+)')
_TEXT_LHS = re.compile(r'\blhs_contracting_dims=\{([\d,]*)\}')
_TEXT_LABELS = re.compile(r'\bdim_labels=(\w+)_(\w+)->(\w+)')
_TEXT_WINDOW = re.compile(r'\bwindow=\{([^}]*)\}')


def _closing(text: str, start: int) -> int:
    """The index of the bracket that closes the one at `start`."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] in '([{':
            depth += 1
        elif text[i] in ')]}':
            depth -= 1
            if not depth:
                return i
    return len(text)


def _text_operands(text: str) -> List[str]:
    names, depth, word = [], 0, ''
    for ch in text + ',':
        if ch in '([{':
            depth += 1
        elif ch in ')]}':
            depth -= 1
        if ch == ',' and not depth:
            names.append(word.split()[-1].lstrip('%') if word.split()
                         else '')
            word = ''
        else:
            word += ch
    return names


def _text_window(attrs: str, n: int) -> List[Tuple[int, ...]]:
    """(size, stride, low padding, kernel dilation, input dilation) of each
    of `n` spatial dimensions from `window={size=64x8 stride=63x7 pad=0_0x1_1
    lhs_dilate=64x8 rhs_dilate=1x1}`; what is not written is 1 (padding 0)."""
    m = _TEXT_WINDOW.search(attrs)
    given = dict(part.split('=') for part in m.group(1).split()) if m else {}

    def of(key, default):
        return [int(v.split('_')[0]) for v in given[key].split('x')] \
            if key in given else [default] * n
    return list(zip(of('size', 1), of('stride', 1), of('pad', 0),
                    of('rhs_dilate', 1), of('lhs_dilate', 1)))


def hlo_text_computations(hlo_text: str) -> Dict[str, list]:
    """The module of compiled HLO text in the counting rule's form."""
    comps: Dict[str, list] = {}
    dims_of: Dict[str, Tuple[int, ...]] = {}
    pending = []      # products, finished when every shape is known
    current = None
    for line in hlo_text.splitlines():
        m = _TEXT_NAME.match(line)
        if m is None:
            header = _TEXT_HEADER.match(line)
            if header:
                current = comps.setdefault(header.group(1), [])
            continue
        if current is None:
            continue
        name, rest = m.group(1), line[m.end():]
        if rest.startswith('('):              # a tuple's shape
            dims, at = (), _closing(rest, 0) + 1
        else:
            at = rest.index(' ') if ' ' in rest else len(rest)
            d = _TEXT_DIMS.match(rest)
            dims = tuple(int(x.lstrip('<=')) for x in d.group(1).split(',')
                         if x) if d else ()
        dims_of[name] = dims
        rest = rest[at:].lstrip()
        paren = rest.find('(')
        opcode = rest[:paren]
        end = _closing(rest, paren)
        attrs = rest[end + 1:].split(', metadata={', 1)[0]
        kind = _TEXT_KIND.search(attrs) if opcode == 'fusion' else None
        ins = [name, opcode, kind.group(1) if kind else '', dims, (),
               _TEXT_CALLS.findall(attrs) if opcode == 'fusion' else []]
        current.append(ins)
        if opcode in PRODUCT_OPCODES:
            pending.append((ins, _text_operands(rest[paren + 1:end]), attrs))
    for ins, operands, attrs in pending:   # an operand may be defined below
        lhs, rhs = (dims_of[o] for o in operands[:2])
        if ins[1] == 'dot':
            axes = _TEXT_LHS.search(attrs)
            ins[3] += tuple(lhs[int(a)] for a in
                            (axes.group(1).split(',') if axes else ()) if a)
            continue
        labels = _TEXT_LABELS.search(attrs).groups()
        n = sum(c.isdigit() for c in labels[0])
        sizes = [[dims[labels[side].index(str(d))] for d in range(n)]
                 for side, dims in enumerate((lhs, rhs, ins[3]))]
        ins[4] = tuple(
            (sizes[0][d], sizes[2][d], *w)
            for d, w in enumerate(_text_window(attrs, n)))
        ins[3] = tuple(e for c, e in zip(labels[2], ins[3])
                       if not c.isdigit()) + (rhs[labels[1].index('i')],)
    return {c: [tuple(i) for i in rows] for c, rows in comps.items()}
