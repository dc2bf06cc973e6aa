"""telemetry-key checker: counter spec/docs lockstep (docs/ANALYSIS.md).

Collects every statically reachable telemetry emit in the package:

  * flat always-on counters -- `trace.metric` / `telemetry.metric`
    call sites (string literals; `%`/f-string/`+` formats become
    wildcard patterns, so `'fallback.escalated.w%d' % W` still counts);
  * phase counters and spans -- `trace.count` / `phase_count` /
    `trace.span` names (they satisfy doc rows but are not pre-seeded);
    flight-recorder event stamps (`recorder.record`) count the same
    way, so the docs' event catalog stays in lockstep with the sites;
  * registry families -- `registry.counter/gauge/histogram('amtpu_*')`.

Then enforces three invariants:

  1. every literal flat key whose prefix owns a ``KNOWN_*_KEYS`` block
     (fallback/collect/resilience/scheduler/resident/pipeline/mesh)
     must be pre-seeded there -- a gate reading the bench block must
     see an explicit zero, not a missing key.  Dynamic keys must match
     a declared `DYNAMIC_KEY_PATTERNS` family;
  2. every flat key and registry family must have a glossary row in
     docs/OBSERVABILITY.md or docs/RESILIENCE.md (digit runs collapse
     to `N`, so `fallback.escalated.w16` matches the documented
     `fallback.escalated.wN`);
  3. pre-seeded and documented keys with NO emit site are dead --
     flagged so the spec and the docs shrink with the code.
"""

import ast
import os
import re

from .engine import Finding, register

CHECKER = 'telemetry-key'

#: flat-counter prefix -> the telemetry/__init__.py KNOWN tuple that
#: pre-seeds it into every bench_block / healthz payload.  Prefixes may
#: span multiple dot segments (`sync.fanout`); the LONGEST matching
#: prefix owns a key, and the seeded suffix is what follows it.
PRESEED_BLOCKS = {
    'fallback': 'KNOWN_FALLBACK_REASONS',
    'collect': 'KNOWN_COLLECT_KEYS',
    'resident': 'KNOWN_RESIDENT_BATCH_KEYS',
    'pipeline': 'KNOWN_PIPELINE_KEYS',
    'mesh': 'KNOWN_MESH_KEYS',
    'resilience': 'KNOWN_RESILIENCE_KEYS',
    'scheduler': 'KNOWN_SCHEDULER_KEYS',
    'gateway': 'KNOWN_GATEWAY_KEYS',
    'sync.fanout': 'KNOWN_FANOUT_KEYS',
    'egress': 'KNOWN_EGRESS_KEYS',
    'storage': 'KNOWN_STORAGE_KEYS',
    'recorder': 'KNOWN_RECORDER_KEYS',
    'slo': 'KNOWN_SLO_KEYS',
    'capacity': 'KNOWN_CAPACITY_KEYS',
    'trace': 'KNOWN_TRACE_KEYS',
    'fleet': 'KNOWN_FLEET_KEYS',
    'router': 'KNOWN_ROUTER_KEYS',
    'migrate': 'KNOWN_MIGRATE_KEYS',
    'failover': 'KNOWN_FAILOVER_KEYS',
    'readview': 'KNOWN_READVIEW_KEYS',
}


def _preseed_ns_of(key):
    """The longest PRESEED_BLOCKS prefix owning `key`, or None."""
    best = None
    for ns in PRESEED_BLOCKS:
        if key.startswith(ns + '.') and (best is None
                                         or len(ns) > len(best)):
            best = ns
    return best

#: dynamic key families that are deliberately NOT pre-seeded row by row
#: (`*` matches within and across dots); everything else formatted at
#: runtime must land on a pre-seeded literal
DYNAMIC_KEY_PATTERNS = (
    'fallback.escalated.w*',        # tier ladder: one key per width
    'resilience.fault_injected.*',  # per-site subkeys (base is seeded)
    '*.latch_flip_ignored',         # resident./mesh. via namespace map
    'jit.compiles.*',               # one key per compiled function
)

#: counter namespaces whose doc glossary rows are checked for deadness
#: (first dot segment of each preseed prefix, plus the un-seeded ones)
DOC_NAMESPACES = tuple(sorted({ns.split('.')[0]
                               for ns in PRESEED_BLOCKS})) + (
    'sched', 'sidecar', 'device', 'host', 'hostfull', 'hostreg',
    'sanitize', 'pallas', 'ops', 'transfer', 'jit', 'pool')

_TOKEN_RE = re.compile(r'`([A-Za-z0-9_./*%\[\]]+)`')
_KEY_RE = re.compile(r'^[a-z][a-z0-9_]*(\.[a-zA-Z0-9_.*]+)+$')
_BARE_RE = re.compile(r'^\.?[a-z][a-zA-Z0-9_]*$')


def _pattern_of(node):
    """(literal, regex) for a key expression: literal keys return
    (key, None); formatted keys return (None, compiled_regex); opaque
    expressions return (None, None)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value, None
    lit = None
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod) \
            and isinstance(node.left, ast.Constant) \
            and isinstance(node.left.value, str):
        lit = re.sub(r'%[-#0-9.]*[sdifrxX]', '*', node.left.value)
    elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add) \
            and isinstance(node.left, ast.Constant) \
            and isinstance(node.left.value, str):
        lit = node.left.value + '*'
    elif isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant):
                parts.append(str(v.value))
            else:
                parts.append('*')
        lit = ''.join(parts)
    if lit is None:
        return None, None
    return None, _glob_re(lit)


def _glob_re(glob):
    return re.compile('^' + '.*'.join(re.escape(p)
                                      for p in glob.split('*')) + '$')


def _terminal_name(func):
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _collect_emits(sources):
    """(flat_literals, flat_patterns, phase_names, families) --
    flat_literals: {key: (path, line)}; flat_patterns: [(regex, path,
    line)]; phase_names: set of span/count names; families: {name:
    (path, line)}."""
    flats, patterns, phases, families = {}, [], set(), {}
    pkg_self = os.path.join('automerge_tpu', 'analysis') + os.sep
    for src in sources:
        if src.relpath.startswith(pkg_self) \
                and os.path.basename(src.path) != 'sanitize.py':
            # the CHECKER modules quote key literals in messages and
            # pattern tables; sanitize.py is product runtime whose
            # emits count like any other
            continue
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            name = _terminal_name(node.func)
            if name == 'metric':
                lit, pat = _pattern_of(node.args[0])
                if lit is not None:
                    flats.setdefault(lit, (src.path, node.lineno))
                elif pat is not None:
                    patterns.append((pat, src.path, node.lineno))
            elif name in ('count', 'phase_count', 'span', 'phase_add',
                          'span_with_context', 'fire', 'arm', 'record'):
                lit, pat = _pattern_of(node.args[0])
                if lit is not None:
                    phases.add(lit)
                elif pat is not None:
                    patterns.append((pat, src.path, node.lineno))
            elif name in ('counter', 'gauge', 'histogram'):
                lit, _ = _pattern_of(node.args[0])
                if lit is not None and lit.startswith('amtpu_'):
                    families.setdefault(lit, (src.path, node.lineno))
    return flats, patterns, phases, families


def _parse_known_blocks(sources):
    """{tuple_name: (set_of_keys, path, line)} from telemetry/__init__."""
    out = {}
    for src in sources:
        if not src.relpath.replace(os.sep, '/').endswith(
                'telemetry/__init__.py'):
            continue
        for node in src.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id.startswith('KNOWN_') \
                    and isinstance(node.value, (ast.Tuple, ast.List)):
                keys = {e.value for e in node.value.elts
                        if isinstance(e, ast.Constant)}
                out[node.targets[0].id] = (keys, src.path, node.lineno)
    return out


def _doc_tokens(ctx):
    """Documented counter keys from the two glossaries, with slash
    continuation: in `` `collect.conflict_sparse` / `conflict_dense` ``
    (or `` `sidecar.client.respawns` / `.transport_errors` ``) the
    continuation inherits the previous token's namespace -- but ONLY
    when separated by a bare slash, so prose backticks never fabricate
    keys.  A trailing ``[...]`` qualifier is stripped
    (`resilience.fault_injected[.site]`); tokens containing ``*`` are
    doc-side wildcard families."""
    tokens = {}
    gap_re = re.compile(r'^\s*/\s*$')
    for rel in ('docs/OBSERVABILITY.md', 'docs/RESILIENCE.md'):
        text = ctx.doc_text(rel)
        for ln, line in enumerate(text.splitlines(), 1):
            prefix, last_end = None, -1
            for m in _TOKEN_RE.finditer(line):
                tok = m.group(1).split('[')[0].rstrip('.')
                continues = prefix is not None and gap_re.match(
                    line[last_end:m.start()])
                if _KEY_RE.match(tok) and tok.split('.')[0] \
                        in DOC_NAMESPACES and not re.search(r'[A-Z]{2}',
                                                            tok):
                    tokens.setdefault(tok, (rel, ln))
                    prefix, last_end = tok.rsplit('.', 1)[0], m.end()
                elif continues and _BARE_RE.match(tok) \
                        and not tok.startswith('amtpu'):
                    full = prefix + tok if tok.startswith('.') \
                        else '%s.%s' % (prefix, tok)
                    tokens.setdefault(full, (rel, ln))
                    last_end = m.end()
                else:
                    prefix = None
    return tokens


def _canonical(key):
    """Digit runs collapse to N so `fallback.escalated.w16` matches the
    documented `fallback.escalated.wN`."""
    return re.sub(r'\d+', 'N', key)


def _emitted(key, flats, patterns, phases):
    if key in flats or key in phases:
        return True
    return any(pat.match(key) for pat, _p, _l in patterns)


@register(CHECKER)
def check(sources, ctx):
    findings = []
    flats, patterns, phases, families = _collect_emits(sources)
    known = _parse_known_blocks(sources)
    docs = _doc_tokens(ctx)
    doc_keys = {k for k in docs if '*' not in k}
    doc_globs = {k: _glob_re(k) for k in docs if '*' in k}
    # a whole-namespace glob (`resident.*`) keeps its row alive but is
    # too broad to DOCUMENT a key -- membership needs two literal
    # segments (`sidecar.client.*`)
    doc_globs_member = {k: g for k, g in doc_globs.items()
                        if k.split('*')[0].count('.') >= 2}
    doc_canon = {_canonical(k) for k in doc_keys}
    dynamic_res = [_glob_re(p) for p in DYNAMIC_KEY_PATTERNS]

    # 1. every literal flat emit with a pre-seeded prefix is in KNOWN
    for key, (path, line) in sorted(flats.items()):
        ns = _preseed_ns_of(key)
        block = PRESEED_BLOCKS.get(ns) if ns else None
        if block is not None:
            suffix = key[len(ns) + 1:]
            keys, _bp, _bl = known.get(block, (set(), None, 0))
            if suffix not in keys \
                    and not any(r.match(key) for r in dynamic_res):
                findings.append(Finding(
                    CHECKER, 'unseeded-key', path, line,
                    '%s is emitted but not pre-seeded in telemetry.%s '
                    '-- gates would see a missing key instead of an '
                    'explicit zero' % (key, block)))
        # 2. documented somewhere
        if key not in doc_keys and _canonical(key) not in doc_canon \
                and not any(g.match(key)
                            for g in doc_globs_member.values()):
            findings.append(Finding(
                CHECKER, 'undocumented-key', path, line,
                '%s has no glossary row in docs/OBSERVABILITY.md or '
                'docs/RESILIENCE.md' % key))

    # formatted emits with a pre-seeded namespace must match a declared
    # dynamic family (otherwise the runtime key can never be seeded)
    for pat, path, line in patterns:
        glob = pat.pattern
        ns_m = re.match(r'\^([a-z_]+)\\\.', glob)
        if ns_m and ns_m.group(1) in PRESEED_BLOCKS:
            sample = glob[1:-1].replace('\\', '').replace('.*', 'X')
            if not any(r.match(sample) for r in dynamic_res):
                findings.append(Finding(
                    CHECKER, 'undeclared-dynamic-key', path, line,
                    'formatted %s.* key does not match any '
                    'DYNAMIC_KEY_PATTERNS family' % ns_m.group(1)))

    # 3a. pre-seeded keys with no emit site are dead
    for ns, block in sorted(PRESEED_BLOCKS.items()):
        keys, bpath, bline = known.get(block, (set(), None, 0))
        for suffix in sorted(keys):
            key = '%s.%s' % (ns, suffix)
            if not _emitted(key, flats, patterns, phases):
                findings.append(Finding(
                    CHECKER, 'dead-seed', bpath or '<telemetry>', bline,
                    '%s is pre-seeded in %s but nothing emits it'
                    % (key, block)))

    # 3b. documented keys with no emit site are dead rows
    emitted_canon = {_canonical(k) for k in flats} \
        | {_canonical(k) for k in phases}
    for tok, (rel, ln) in sorted(docs.items()):
        if '*' in tok:
            # a documented wildcard family is live when any emit lands
            # inside it
            glob = doc_globs[tok]
            if not any(glob.match(k) for k in flats) \
                    and not any(glob.match(k) for k in phases):
                findings.append(Finding(
                    CHECKER, 'dead-doc-row',
                    os.path.join(ctx.root, rel), ln,
                    '`%s` is documented but nothing emits inside the '
                    'family' % tok))
            continue
        if _emitted(tok, flats, patterns, phases):
            continue
        if _canonical(tok) in emitted_canon:
            continue
        if any(pat.match(_canonical(tok)) or pat.match(tok)
               for pat, _p, _l in patterns):
            continue
        findings.append(Finding(
            CHECKER, 'dead-doc-row', os.path.join(ctx.root, rel), ln,
            '`%s` is documented but nothing emits it' % tok))

    # registry families must be documented
    text = ctx.doc_text('docs/OBSERVABILITY.md') \
        + ctx.doc_text('docs/RESILIENCE.md')
    for fam, (path, line) in sorted(families.items()):
        if fam not in text:
            findings.append(Finding(
                CHECKER, 'undocumented-family', path, line,
                'registry family %s has no docs/OBSERVABILITY.md row'
                % fam))
    return findings
