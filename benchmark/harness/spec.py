"""Finds everything a cell needs by the names in `BENCHMARK.json`.

    configs/<config>.json        the configuration's sizes and guarantees
    traffic/<traffic>.json       the traffic mix; its `kind` names the
                                 generator kinds/<kind>.py
    workloads/<cell>.json        optional: keys of the mix fixed for this
                                 one cell (its warm-up, say)
    metrics/<metric>.py          one reader per metric, `read(ctx)`
    doctypes/<doc_type>.py       the document model a configuration names

A new configuration, traffic mix, cell or metric is a new file and a new
entry in `BENCHMARK.json`; nothing here lists names.
"""

import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(Exception):
    pass


def _json(path):
    with open(path) as f:
        return json.load(f)


def load(root, workload):
    """The cell named `workload`, resolved into plain data."""
    path = os.path.join(root, 'BENCHMARK.json')
    if not os.path.exists(path):
        raise SpecError('no BENCHMARK.json in %s' % root)
    bench = _json(path)
    cells = {w['name']: w for w in bench['workloads']}
    if workload not in cells:
        raise SpecError('no workload %r in BENCHMARK.json' % workload)
    w = cells[workload]
    cfg_entry = {c['name']: c for c in bench['configs']}[w['config']]
    traffic = _json(os.path.join(HERE, 'traffic', w['traffic'] + '.json'))
    pinned = os.path.join(HERE, 'workloads', workload + '.json')
    if os.path.exists(pinned):
        traffic.update(_json(pinned))

    def applies(m):
        return 'workloads' not in m or workload in m['workloads']
    return {'name': workload, 'chips': w['chips'],
            'config': _json(os.path.join(root, cfg_entry['file'])),
            'traffic': traffic,
            'end_to_end': [m for m in bench['end_to_end'] if applies(m)],
            'per_layer': [m for m in bench['per_layer'] if applies(m)]}


def kind(cell):
    """The traffic generator module the cell's mix names."""
    return importlib.import_module('kinds.' + cell['traffic']['kind'])


def doctype(cfg):
    return importlib.import_module('doctypes.' + cfg['doc_type'])


def reader(name):
    """`read(ctx)` of metrics/<name>.py."""
    path = os.path.join(HERE, 'metrics', name + '.py')
    if not os.path.exists(path):
        raise SpecError('no reader for metric %r' % name)
    mod_name = 'metric_' + name.replace('.', '_').replace('-', '_')
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
