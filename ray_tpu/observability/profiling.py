"""``ray timeline``: what this process traced, as a Chrome trace.

Reference: ``ray timeline`` (python/ray/state.py:239 profile_table →
chrome_tracing_dump) renders chrome://tracing JSON from the spans each
worker buffered. Here the one span system that is written to is
``util/tracing.py``; ``timeline()`` renders the finished spans of its
bounded buffer as complete events, so it is empty until
``tracing.setup_tracing()`` (or a sampled remote trace) records some.
`cli.py timeline` merges every node's spans the same way
(flight_recorder.merge_chrome_trace).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Union

from ray_tpu.observability.flight_recorder import chrome_span_event
from ray_tpu.util import tracing


def timeline(filename: Optional[str] = None
             ) -> Union[List[Dict[str, Any]], str]:
    """Chrome trace events (a list), or the file they were written to."""
    pid = os.getpid()
    events = [chrome_span_event(span.to_dict(), pid)
              for span in tracing.get_buffered_spans()
              if span.end_time is not None]
    if filename is None:
        return events
    with open(filename, "w") as f:
        json.dump(events, f, default=str)
    return filename
