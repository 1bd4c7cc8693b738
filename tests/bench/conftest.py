import sys
from pathlib import Path

# the benchmark's package lives at the repository root
REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
