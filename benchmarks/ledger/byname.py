"""Finding the benchmark's pieces by the names the data files give."""
import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_module(kind, name):
    """``<kind>/<name>.py`` beside this file."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"ledger_{kind}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(dotted):
    """``package.module:attribute`` -> the object."""
    module, attr = dotted.split(":")
    return getattr(importlib.import_module(module), attr)
