"""Put the checkout root (for ``bench``) and ``src`` (for ``repro``) on the
path, whatever directory pytest was started from."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
