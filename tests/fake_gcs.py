"""Shim: the fake GCS server moved into the package (devtools) so the
devstack can ship it; tests keep this import/exec path."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from metaflow_tpu.devtools.fake_gcs import *  # noqa: F401,F403
from metaflow_tpu.devtools.fake_gcs import FakeGCSServer, FakeGCSState, main

if __name__ == "__main__":
    main()
