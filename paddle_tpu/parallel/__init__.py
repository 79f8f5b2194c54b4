from ..mesh import make_mesh  # noqa: F401
from .parallel_executor import ParallelExecutor  # noqa: F401
from .program_pipeline import ProgramPipeline  # noqa: F401
from .partitioner import DistributeTranspiler, ShardingRules  # noqa: F401
