"""deploy_servers_s: seconds of set-up in ``repro.deploy.server`` spans:
every district's index build and shortcut install, summed."""
from chipbench.spans import deploy_servers_s as read  # noqa: F401
