"""deploy_center_s: seconds of set-up in the ``repro.deploy.center``
span: the computing center's border-label build."""
from chipbench.spans import deploy_center_s as read  # noqa: F401
