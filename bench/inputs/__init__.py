"""Input formats, one file each (``bench/inputs/<input>.py``), found by
the traffic file's ``input``.

A format module has one function, ``write(graph, config, traffic,
workdir) -> Files``: it writes the file(s) the window's loads read and
says how to open them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List


@dataclasses.dataclass(frozen=True)
class Files:
    paths: List[str]              # load k reads paths[k % len(paths)]
    input_bytes: int              # bytes of the file one load reads
    open_kwargs: Dict[str, Any]   # for open_graph(path, **open_kwargs)

    def path(self, k: int) -> str:
        return self.paths[k % len(self.paths)]
