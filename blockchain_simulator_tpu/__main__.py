"""``python -m blockchain_simulator_tpu`` — see cli.py."""

import sys

from blockchain_simulator_tpu.cli import main
from blockchain_simulator_tpu.utils import aotcache

# the process entry point, not cli.main(): tests call main() in-process and
# must not have a process-wide compile cache switched on under them
aotcache.enable_xla_cache()
sys.exit(main())
