"""A planted fault (test_range_faults.py puts this directory on the
server child's PYTHONPATH): the program's BSI comparison answers ``>`` as
``>=``. Python imports a ``sitecustomize`` at start-up; this one waits for
``pilosa_tpu.ops.bsi`` to be imported and wraps its ``compare``. Processes
that never import that module (the load generators) are left alone."""

import importlib.abc
import importlib.util
import sys

TARGET = "pilosa_tpu.ops.bsi"


class _Plant(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path, target=None):
        if name != TARGET:
            return None
        sys.meta_path.remove(self)
        spec = importlib.util.find_spec(name)
        load = spec.loader.exec_module

        def exec_module(module):
            load(module)
            sound = module.compare
            module.compare = lambda slices, op, value: sound(slices, ">=" if op == ">" else op, value)

        spec.loader.exec_module = exec_module
        return spec


sys.meta_path.insert(0, _Plant())
